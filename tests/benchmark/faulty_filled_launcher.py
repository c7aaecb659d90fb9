"""`lib/launch_filled.py` with the restore broken underneath, for
test_filled.py (and the builder's proof on the chip): the launcher has
to refuse the node, and the harness' run has to fail.

    FAULTY_FILLED_FAULT=one_row_short     the Loader withholds the
                                          snapshot's last row
    FAULTY_FILLED_FAULT=wrong_remaining   one sampled row is restored
                                          with one more remaining than
                                          the snapshot says
    FAULTY_FILLED_FAULT=no_columnar_load  the engine knows load() alone
                                          (a program without the bulk
                                          path, ItemColumns still there)
    FAULTY_FILLED_FAULT=no_item_columns   the program's Loader protocol
                                          has no columns (the parent of
                                          PR 28)
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))


def plant(fault: str) -> None:
    from lib import launch_filled
    from lib.snapshot import CHUNK_ROWS

    loader = launch_filled.SnapshotLoader
    if fault == "one_row_short":
        init = loader.__init__
        loader.__init__ = lambda self, snapshot, rows: init(
            self, snapshot, rows - 1)
    elif fault == "wrong_remaining":
        real = loader.load_columns

        def altered(self):
            row = int(self.snapshot.sample()[-1])
            for n, cols in enumerate(real(self)):
                if n == row // CHUNK_ROWS:
                    cols.remaining[row % CHUNK_ROWS] += 1
                    cols.remf_hi[row % CHUNK_ROWS] += 1
                yield cols

        loader.load_columns = altered
    elif fault == "no_columnar_load":
        from gubernator_tpu.core.engine import DecisionEngine

        real_load = DecisionEngine.load

        def per_item(self, ldr):
            class ItemsOnly:  # what such an engine sees of a Loader
                load, save = ldr.load, ldr.save

            return real_load(self, ItemsOnly())

        DecisionEngine.load = per_item
    elif fault == "no_item_columns":
        import gubernator_tpu.store

        del gubernator_tpu.store.ItemColumns
    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    plant(os.environ["FAULTY_FILLED_FAULT"])
    from lib import launch_filled

    sys.exit(launch_filled.main())
