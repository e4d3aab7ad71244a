"""Standalone (user-source) template project end-to-end.

Round-1 gap: every bundled template pointed at
engines built into the framework; nothing proved a template with its OWN
DASE source — the product's third-party authorship path — trains and
serves. This drives the real `pio` binary: template get → app new →
import → build → train → deploy → query, with all components resolved
from the copied project directory (reference: upstream
template-scala-parallel-vanilla checkout workflow, SURVEY.md §2.8).
"""

import json
import os
import socket
import subprocess
import sys
import time
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PIO = os.path.join(REPO, "bin", "pio")


def run_pio(args, env, check=True, cwd=None):
    r = subprocess.run(
        [PIO, *args], capture_output=True, text=True, env=env, timeout=300,
        cwd=cwd,
    )
    if check and r.returncode != 0:
        raise AssertionError(
            f"pio {' '.join(args)} failed ({r.returncode}):\n{r.stdout}\n{r.stderr}"
        )
    return r


@pytest.fixture()
def cli_env(tmp_path):
    env = dict(os.environ)
    env["PIO_FS_BASEDIR"] = str(tmp_path / "store")
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_user_source_template_lifecycle(cli_env, tmp_path):
    # pio template get vanilla <dir> — copy the self-contained project.
    proj = str(tmp_path / "MyEngine")
    run_pio(["template", "get", "vanilla", proj], cli_env)
    assert os.path.exists(os.path.join(proj, "vanilla_engine.py"))

    # The engine source must come from the PROJECT, not the framework.
    src = open(os.path.join(proj, "vanilla_engine.py")).read()
    imports = [l for l in src.splitlines()
               if l.startswith(("import ", "from "))]
    assert not any("incubator_predictionio_tpu.models" in l
                   for l in imports), imports

    run_pio(["app", "new", "MyApp1"], cli_env)

    events = tmp_path / "events.jsonl"
    with open(events, "w") as f:
        k = 0
        for u in range(6):
            for i in range(8):
                if (u + i) % 2 == 0:
                    f.write(json.dumps({
                        "event": "view" if i % 3 else "rate",
                        "entityType": "user", "entityId": f"u{u}",
                        "targetEntityType": "item", "targetEntityId": f"i{i}",
                        "properties": {} if i % 3 else {"rating": 5},
                        "eventTime": f"2024-01-01T00:00:{k:02d}.000Z",
                    }) + "\n")
                    k += 1
    run_pio(["import", "--app-name", "MyApp1", "--input", str(events)],
            cli_env)

    run_pio(["build", "--engine-dir", proj], cli_env)
    r = run_pio(["train", "--engine-dir", proj], cli_env)
    assert "Training completed" in r.stdout

    port = _free_port()
    server = subprocess.Popen(
        [PIO, "deploy", "--engine-dir", proj, "--port", str(port)],
        env=cli_env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )
    try:
        deadline = time.time() + 120
        last_err = None
        while time.time() < deadline:
            try:
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/queries.json",
                    data=json.dumps({"user": "u0", "num": 3}).encode(),
                    headers={"Content-Type": "application/json"},
                )
                with urllib.request.urlopen(req, timeout=5) as resp:
                    body = json.loads(resp.read())
                break
            except Exception as e:  # server still warming up
                last_err = e
                if server.poll() is not None:
                    raise AssertionError(
                        f"deploy died: {server.stdout.read()}")
                time.sleep(1)
        else:
            raise AssertionError(f"server never answered: {last_err}")

        scores = body["itemScores"]
        assert len(scores) == 3
        # Popularity order: "i0" is rated 5 by the most users.
        assert scores[0]["item"] == "i0"
        assert scores[0]["score"] >= scores[1]["score"] >= scores[2]["score"]
    finally:
        server.terminate()
        server.wait(timeout=30)
