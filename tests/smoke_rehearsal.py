"""CPU rehearsal of ``chip_smoke.py`` at a tiny size.

    python tests/smoke_rehearsal.py [--chips 4]

The on-chip-measurement guide's first two rehearsals: the smoke's whole
control flow — spawned producers, window stream, Trainer, checkpoint
and resume, and with ``--chips 4`` the ICI fan-out, the dp x fsdp pair
and the device exchange on four virtual devices — with Pallas kernels in
interpret mode.  The size and the platform are overridden HERE, in the
rehearsal, not by an option of the program; nothing it prints is a
device number and it prints no result line.
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402  (module-level producers: picklable by name)

TINY = chip_smoke.Sizes(
    stream_rows=64, stream_cols=8, stream_batch=16, stream_windows=8,
    lookahead=3,
    vocab=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128,
    seq=128, batch=4, steps_per_window=2, train_windows=3,
    mc_layers=2, mc_windows=4, shuffle_rows=16, shuffle_rounds=3,
)


def main() -> None:
    chips = 4 if sys.argv[1:] == ["--chips", "4"] else 1
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={chips}"
    )
    from ddl_tpu.bringup import bring_up

    bring_up("cpu")
    compiles = chip_smoke.CompileLog()
    chip_smoke.announce()
    run = chip_smoke.one_chip if chips == 1 else chip_smoke.four_chips
    run(TINY, 0, compiles)
    print(f"rehearsal ok ({chips} virtual device(s), CPU)")


if __name__ == "__main__":
    main()
