"""Every example script must run end-to-end in --smoke mode (subprocess,
CPU backend) — the user-facing flows stay alive."""
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_EXAMPLES = sorted(
    f for f in os.listdir(os.path.join(_REPO, "examples"))
    if f.endswith(".py") and not f.startswith("_"))


@pytest.mark.parametrize("script", _EXAMPLES)
def test_example_smoke(script, tmp_path):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_"))}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    path = os.path.join(_REPO, "examples", script)
    runner = (
        "import runpy, sys\n"
        f"sys.argv = [{path!r}, '--smoke']\n"
        f"runpy.run_path({path!r}, run_name='__main__')\n")
    out = subprocess.run(
        [sys.executable, "-c", runner],
        capture_output=True, text=True, timeout=420, cwd=str(tmp_path),
        env=env)
    assert out.returncode == 0, f"{script}:\n{out.stdout}\n{out.stderr}"
