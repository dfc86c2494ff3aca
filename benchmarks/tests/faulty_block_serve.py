#!/usr/bin/env python3
"""harness/serve.py with an older block's streams broken underneath, for
test_ranges_cell_cpu.py: the flush encodes every series of each block
that started more than 14 h before the service did (the oldest of the
seven that ``tsbs-cpu-14h`` loads; the second oldest too in 11 minutes of
every 2 h) with its first value one unit in the last place off. The
answers stay within the cell's tolerance; only the stream check, bit for
bit over every block, can see it."""

import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.getcwd())
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "harness"))


def plant() -> None:
    import numpy as np

    from m3_tpu.encoding.m3tsz import hostpath

    older = time.time_ns() - 14 * 3600 * 1_000_000_000
    encode = hostpath.encode_blocks_ragged

    def faulty_encode(times, vbits, offsets, block_starts, *a, **k):
        if len(block_starts) and int(block_starts[0]) < older:
            vbits = np.array(vbits, copy=True)
            firsts = np.asarray(offsets[:-1])[np.diff(offsets) > 0]
            vbits[firsts] += np.uint64(1)
        return encode(times, vbits, offsets, block_starts, *a, **k)

    hostpath.encode_blocks_ragged = faulty_encode


if __name__ == "__main__":
    plant()
    import serve

    serve.main()
