"""The per-item validity pass over R block rows, W workers and T tags.

Operations: the two violation contractions, each R x T by T x W at two
operations per multiply-add.  Bytes: each input read once (one byte per
mask or tag cell, four per number) and the R x W validity written, one
byte per cell: here it is the result the caller reads.
"""


def ops_bytes(R: int, W: int, T: int):
    ops = 2 * (2 * R * W * T)
    nbytes = (W * T          # which tags run on which worker
              + R * T        # affinity terms of each row
              + R * W        # candidate mask
              + 3 * 4 * W    # memory used, memory size, running instances
              + 3 * 4 * R    # function memory, capacity, concurrency
              + R * W)       # validity written
    return ops, nbytes
