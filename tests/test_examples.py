"""Every runnable demo in examples/ must stay runnable — each is a
documented drive of a product surface (the sentinel-demo analog), and a
silent bit-rot there is a broken front door. Each demo self-terminates
and runs on the CPU backend via examples/_bootstrap.py.
"""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

EXAMPLES_DIR = os.path.join(os.path.dirname(__file__), "..", "examples")
DEMOS = sorted(
    f for f in os.listdir(EXAMPLES_DIR)
    if f.endswith(".py") and not f.startswith("_")
)


@pytest.mark.parametrize("demo", DEMOS)
def test_example_runs_clean(demo):
    if demo == "mesh_demo.py":
        from sentinel_tpu.parallel import mesh_unavailable_reason

        reason = mesh_unavailable_reason(8)
        if reason:
            pytest.skip(reason)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["SENTINEL_DEMO_PORT"] = "0"  # ephemeral ports: no collisions
    env["SENTINEL_DEMO_DURATION"] = "2"  # shorten long traffic loops
    r = subprocess.run(
        [sys.executable, demo],
        cwd=EXAMPLES_DIR,
        env=env,
        capture_output=True,
        text=True,
        timeout=240,
    )
    assert r.returncode == 0, (
        f"{demo} exited {r.returncode}\n--- stdout ---\n{r.stdout[-2000:]}"
        f"\n--- stderr ---\n{r.stderr[-2000:]}"
    )
    assert "Traceback" not in r.stderr, r.stderr[-2000:]
