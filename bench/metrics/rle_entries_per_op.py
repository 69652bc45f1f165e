"""Entries of its run-length row that an op took on the device, over the window: the planes' `rle_entries_appended`
(what each row's occupied entries grew by, read with every flush cycle's health readback) over the ops flushed, which
in a cell whose `fast_path_share` is 0 are `flush_slow_ops`. A keystroke inside a run takes two (the run splits, the
unit is an entry), one typed after it takes one, a delete of a unit that is an entry of its own none; the host projects
one an op and the growth check (`lib/room.py`) allows for two.

It reads how long a row lasts before defragmentation is due, and moves no tail in a cell whose kernels sweep all R
entries of a row and whose window never reaches compaction: `moves` names the cell's end-to-end metric because an
entry has to name one. None on a unit arena and on a program without the counter."""

SOURCE = "program_counter"


def read(run):
    delta = run["plane_delta"]
    flushed = delta.get("flush_fast_ops", 0) + delta.get("flush_slow_ops", 0)
    if run.get("arena") != "rle" or "rle_entries_appended" not in delta or not flushed:
        return None
    return delta["rle_entries_appended"] / flushed
