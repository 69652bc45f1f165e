"""The growth check of a run, made before anything boots: could the hottest
document of an open loop outgrow its row of the device arena inside the run?

The flags are read as the program reads them (`hocuspocus_tpu.cli`'s own
parser: a flag left out takes the CLI's default, one given twice is read as
`argparse` reads it), and a row's room is counted in the arena's own unit:

  --tpu-arena unit   `--tpu-capacity` counts UTF-16 units. A document
                     starts with what its kind's `first_in_row(config,
                     "unit")` says (a text: `doc_units`) and grows by what its
                     generator's `most_units_added(mix, all_docs, seconds)` says.
  --tpu-arena rle    `--tpu-capacity` counts entries, one a run. A document
                     starts with its kind's `first_in_row(config, "rle")` (a
                     text: the one entry of its one string item) and grows by
                     the generator's `most_entries_added(mix, all_docs,
                     seconds)`. An operation flags a row's overflow unless two
                     entries are free before it (`tpu/kernels_rle.py`:
                     `num_runs + 2 <= r`), whatever it goes on to use: a row
                     that holds the first state and that bound never comes to
                     that.

This is a pre-flight and no more. The device's overflow flag stays the
authority: a document that outgrows its row is retired from the plane, and
the health fact `no_doc_retired` fails the run. A closed loop is not checked,
because what it sends depends on how fast the system answers
(`conflict-midinsert` outgrows its rows past ~2,500 updates/s, PERF.md
section 7).
"""

from __future__ import annotations

import kinds  # bench/lib
from clients import load_generator

COUNTED = {"unit": ("units", "most_units_added"), "rle": ("entries", "most_entries_added")}


def layout(flags: "list[str]") -> "tuple[int, str, int]":
    """(a row's capacity, the arena, the number of planes) as the program
    reads `flags`: `--tpu-shards` planes on one device, else a cell a device."""
    from hocuspocus_tpu.cli import build_parser

    args = build_parser().parse_args(flags)
    planes = args.tpu_shards if args.tpu_devices == 1 else args.tpu_devices
    return args.tpu_capacity, args.tpu_arena, planes


def refusal(config: dict, mix: dict, seconds: float) -> "str | None":
    """Why this configuration cannot be run under this mix for `seconds`, or None."""
    if mix["loop"] != "open":
        return None
    capacity, arena, planes = layout(config["flags"])
    if planes < 1:
        return "--tpu-devices 0 leaves the number of cells to the machine: a configuration states it"
    counted, bound = COUNTED[arena]
    most_added = getattr(load_generator(mix["generator"]), bound, None)
    if most_added is None:
        return (
            f"--tpu-arena {arena} counts a row in {counted} and the generator "
            f"{mix['generator']!r} has no {bound}(mix, all_docs, seconds) to bound a document's growth in them"
        )
    room = capacity - kinds.load(kinds.name_of(config)).first_in_row(config, arena)
    grows = most_added(mix, int(config["driven_docs_per_plane"]) * planes, seconds)
    if grows > room:
        return f"a document could grow by {grows} {counted} and its row has room for {room}"
    return None
