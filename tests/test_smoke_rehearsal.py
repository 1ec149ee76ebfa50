"""``chip_smoke.py`` rehearsed on the CPU, from the files as they are.

The smoke itself needs a TPU (tests/test_bringup.py holds it to
failing without one).  This drives its whole control flow — spawned
producers, window stream, Trainer, checkpoint and resume; with four
virtual devices the ICI fan-out, the dp x fsdp Trainer pair and the
device exchange — at a tiny size, through ``tests/smoke_rehearsal.py``,
where the size and the platform are overridden.  It proves control
flow and bytes, never a device number, and prints no result line.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("chips", [1, 4])
def test_smoke_rehearsal(chips):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "smoke_rehearsal.py")]
        + (["--chips", "4"] if chips == 4 else []),
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, (
        f"rc={proc.returncode}\n{proc.stdout[-3000:]}\n{proc.stderr[-3000:]}"
    )
    assert f"rehearsal ok ({chips} virtual device(s), CPU)" in proc.stdout
    assert '"ok"' not in proc.stdout  # only a chip run prints a result
