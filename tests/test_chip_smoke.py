"""``chip_smoke.py``, rehearsed without the chip.

The script is the driver's check that the system starts on the TPU; here
its two promises that need no chip are held: without a TPU it fails at
the platform check and prints no result, and — with that one check
steered from here, never by an option of the script — every phase's
control flow runs at toy size on the CPU (four virtual devices for the
sharded phase). Nothing here says anything about the chip.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_STEERED = ("import sys, chip_smoke; chip_smoke.PLATFORM = 'cpu'; "
            "sys.exit(chip_smoke.main(sys.argv[1:]))")


def _run(cmd, tmp_path, n_devices):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax-cache"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_devices}")
    return subprocess.run([sys.executable, *cmd], capture_output=True,
                          text=True, timeout=900, env=env, cwd=ROOT)


@pytest.mark.parametrize("args", [["--tiny"], ["--tiny", "--chips", "4"]],
                         ids=["one-chip", "four-chips"])
def test_without_a_tpu_it_fails_and_prints_no_result(tmp_path, args):
    out = _run(["chip_smoke.py", *args], tmp_path, n_devices=4)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs" in out.stderr and "tpu" in out.stderr
    assert "Traceback" not in out.stderr       # a plain message


@pytest.mark.parametrize("chips", [1, 4])
def test_every_phase_runs_at_toy_size(tmp_path, chips):
    out = _run(["-c", _STEERED, "--tiny", "--chips", str(chips)], tmp_path,
               n_devices=chips)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": chips}}
    notes = "\n".join(lines[:-1])
    assert "compile cache at " + str(tmp_path / "jax-cache") in notes
    one_chip = ("train: attention path", "serve/int8: paged attention",
                "serve/float: paged attention")
    four_chips = ("serve/tp4: compiled decode program holds",
                  "spread over 4 devices", "train/tp4: losses/token")
    for phrase in one_chip:
        assert (phrase in notes) == (chips == 1), phrase
    for phrase in four_chips:
        assert (phrase in notes) == (chips == 4), phrase
