"""Traffic kind ``queries``. Set-up: ``serving.serve_setup``. Window: the
load generator (a child that never imports jax) offers the mix's fixed rate,
open loop, for --seconds.

Of the deployment it calls ``engine``, ``engine_params``, ``serve_inputs``,
``release``, ``warmup``, ``bodies``, ``spans`` and ``check_queries``."""

from __future__ import annotations

import time

import run as bench


def phases(record, workdir: str, deployment) -> dict:
    import loadgen
    import serving

    cfg, traffic = record.config, record.traffic
    st, server = serving.serve_setup(record, deployment)
    undo = [bench.wrap_span(record, *target) for target in
            (deployment.spans("queries") if record.traced else ())]
    offer = serving.Offer(st.base, traffic, cfg["n_users"], record.seed,
                          record.seconds, workdir, deployment.bodies)

    def window() -> dict:
        start = offer.go()
        if record.traced:
            lead = float(traffic.get("trace_after_s", 2.0))
            time.sleep(max(0.0, start + lead - time.time()))
            try:
                with bench.Tracer(record, workdir):
                    time.sleep(float(traffic.get("trace_seconds", 4.0)))
            except BaseException:
                offer.kill()
                raise
        return offer.result()

    def after_window(win: dict) -> None:
        lat, late = win["summary"]["latency_ms"], win["summary"]["late_ms"]
        worst = max(range(len(lat)), key=lat.__getitem__)
        bench.say(f"window: request {worst}, due at "
                  f"{offer.sched['due'][worst]:.2f}s, took longest, "
                  f"{lat[worst]:.1f} ms; {sum(v > 100.0 for v in lat)} took "
                  f"over 100 ms; sent at most {max(late):.1f} ms late")
        for u in undo:
            u()
        st.stop()
        server.deployment = None

    def end_to_end(win: dict) -> dict:
        lat = win["summary"]["latency_ms"]
        return {"query_p50_ms": (loadgen.percentile(lat, 50), "ms"),
                "query_p95_ms": (loadgen.percentile(lat, 95), "ms")}

    return {"window": window, "after_window": after_window,
            "check": lambda win: deployment.check_queries(
                cfg, record.seed, offer.sched, offer.keep, win["result"],
                bench.say),
            "end_to_end": end_to_end,
            "attempted": lambda win: (win["summary"]["attempted"],
                                      win["summary"]["failed"])}
