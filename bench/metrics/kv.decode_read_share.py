"""Share of the decode lanes' block-table entries that the paged decode
attention read, over the window's decode steps: the K/V blocks each
step read (where the kernel runs, every lane's live blocks and one
scratch block for a lane with nothing held; where the reference runs,
every entry) summed, over the entries of every lane's block table summed
(decode steps x lanes x max_len / block_tokens). Both come
from the ``kv_blocks`` and ``kv_table_blocks`` of the program's
``round.decode_dispatch`` records whose dispatch returned in the window;
a program that records no such count reports nothing."""

DISPATCH = "round.decode_dispatch"


def read(run):
    if run.spans is None:
        return None
    steps = [
        s for s in run.spans_of(DISPATCH)
        if "kv_blocks" in s and run.in_window(s["t1"])
    ]
    table = sum(s["kv_table_blocks"] for s in steps)
    if not table:
        return None
    return 100.0 * sum(s["kv_blocks"] for s in steps) / table
