"""`pio` CLI console (reference: tools/.../console/Console.scala).

Verbs are registered incrementally as subsystems land; unknown verbs get a
clear not-yet-implemented error instead of a crash. See tools/commands/ for
implementations.

Runtime passthrough (reference: `pio train -- --driver-memory 8G`, the
post-`--` spark-submit tier, SURVEY.md §5.6c): everything after a bare
``--`` configures the XLA/JAX/mesh runtime instead of the verb:

    pio train -- --mesh=4x2 --xla_force_host_platform_device_count=8
    pio deploy -- --jax_platforms=cpu
    pio train -- --jax_default_matmul_precision=float32

    --mesh=D | DxM          device-mesh shape (DxM → 2-D (d, m) ALX mesh)
    --xla_<flag>[=v]        appended to XLA_FLAGS before backend init
    --jax_<option>=v        jax.config.update("jax_<option>", v)
"""

from __future__ import annotations

import os
import sys


def apply_runtime_passthrough(extra: list[str]) -> None:
    """Apply post-`--` runtime args. Must run before the first device
    touch — XLA_FLAGS is read once at backend initialization."""
    xla_flags = []
    for tok in extra:
        if not tok.startswith("--"):
            raise SystemExit(
                f"[error] runtime passthrough args must be --flags, got {tok!r}")
        body = tok[2:]
        key, _, value = body.partition("=")
        if key == "mesh":
            if not value:
                raise SystemExit(
                    "[error] --mesh needs a shape, e.g. --mesh=8 or "
                    "--mesh=4x2")
            os.environ["PIO_MESH_SHAPE"] = value
        elif key.startswith("xla_"):
            xla_flags.append(tok)
        elif key.startswith("jax_"):
            import jax

            v: object
            if not value:
                v = True  # bare --jax_flag means enable (XLA convention)
            elif value.lower() in ("true", "false"):
                v = value.lower() == "true"
            else:
                try:
                    v = int(value)
                except ValueError:
                    try:
                        v = float(value)
                    except ValueError:
                        v = value
            jax.config.update(key, v)
        else:
            raise SystemExit(
                f"[error] unknown runtime passthrough {tok!r} "
                "(expected --mesh=..., --xla_..., or --jax_...)")
    if xla_flags:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " " + " ".join(xla_flags)
        ).strip()


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help", "help"):
        from . import commands

        print(commands.usage())
        return 0
    if argv[0] == "version":
        from incubator_predictionio_tpu import __version__

        print(__version__)
        return 0
    if argv[0] == "lint":
        # static analysis never touches jax/storage — dispatch before
        # anything else so linting a broken runtime stays a pure parse pass
        from .lint.cli import main as lint_main

        return lint_main(argv[1:])
    if argv[0] == "soak":
        # the soak driver only builds argv for subprocesses (which do
        # their own jax setup) — keep the driver process jax-free like
        # lint so the scenario clock never pays a backend init
        from .commands.soak import soak_cmd

        return soak_cmd(argv[1:])
    # (the persistent XLA compilation cache is enabled by the compiling
    # verbs and by WorkflowContext — workflow/context.py — so
    # metadata-only verbs never import jax for it; the platform is
    # whatever JAX selects, JAX_PLATFORMS=cpu for a CPU run)
    from . import commands
    verb_args = argv[1:]
    if "--" in verb_args:
        split = verb_args.index("--")
        apply_runtime_passthrough(verb_args[split + 1:])
        verb_args = verb_args[:split]
    return commands.dispatch(argv[0], verb_args)


if __name__ == "__main__":
    raise SystemExit(main())
