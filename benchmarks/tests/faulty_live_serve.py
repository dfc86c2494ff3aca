#!/usr/bin/env python3
"""harness/serve.py with a guarantee broken underneath, for
test_live_cell_cpu.py: the service acks every sample of a remote-write
request but drops one in a thousand of those whose timestamp is newer
than its own start (the live scrapes; the loaded hour is older), before
the commitlog and the buffer see it."""

import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.getcwd())
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "harness"))


def plant() -> None:
    from m3_tpu.storage.database import Database

    started_ns = time.time_ns()
    seen = {"n": 0}
    write_batch = Database.write_batch

    def lossy_write_batch(self, namespace, entries):
        kept = []
        for e in entries:
            if e[2] >= started_ns:
                seen["n"] += 1
                if seen["n"] % 1000 == 0:
                    continue
            kept.append(e)
        write_batch(self, namespace, kept)
        return [None] * len(entries)

    Database.write_batch = lossy_write_batch


if __name__ == "__main__":
    plant()
    import serve

    serve.main()
