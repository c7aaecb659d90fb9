"""The daemon launcher with the timed path broken underneath, for
test_faults.py: the harness has to see `correct` come out false.

    FAULTY_LAUNCHER_FAULT=alter_answer     one remaining in every fifth
                                           encoded response is one too high
    FAULTY_LAUNCHER_FAULT=state_unchanged  the step never finds a row
                                           occupied: no hit is remembered
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))


def plant(fault: str) -> None:
    if fault == "alter_answer":
        from gubernator_tpu.net import wire_codec

        real, calls = wire_codec.encode_resps, [0]

        def altered(st, lim, rem, rst):
            calls[0] += 1
            if calls[0] % 5 == 0:
                rem = rem.copy()
                rem[0] += 1
            return real(st, lim, rem, rst)

        wire_codec.encode_resps = altered
    elif fault == "state_unchanged":
        from gubernator_tpu.ops import bucket_kernel

        real_occupied = bucket_kernel.meta_occupied
        bucket_kernel.meta_occupied = lambda meta: real_occupied(meta) & False
    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    plant(os.environ["FAULTY_LAUNCHER_FAULT"])
    from lib import launch_daemon

    sys.exit(launch_daemon.main())
