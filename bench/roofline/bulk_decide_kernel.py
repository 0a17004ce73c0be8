"""The fused bulk decide pass over R block rows, W workers and T tags.

Operations: the two violation contractions (affine rows against the tags
missing on each worker, anti-affine rows against the tags present), each
R x T by T x W at two operations per multiply-add.  Bytes: each input read
once at one byte per cell for masks, tag rows and warmth ranks (their
values fit), four bytes per number otherwise, and the winner per row
written; the valid mask and score matrix are outputs no caller needs.
"""


def ops_bytes(R: int, W: int, T: int):
    ops = 2 * (2 * R * W * T)
    nbytes = (W * T          # which tags run on which worker
              + R * T        # affinity terms of each row
              + 2 * R * W    # candidate mask and warmth rank
              + 3 * 4 * W    # memory used, memory size, running instances
              + 4 * 4 * R    # function memory, capacity, concurrency, strategy
              + 4 * R)       # winner
    return ops, nbytes
