"""The kind of document a configuration serves: the optional key
`"document": "<kind>"` of its file, `text` where it names none. A kind is the
file `bench/documents/<kind>.py`, found by name as a generator is
(`lib/clients.py`), and gives the harness everything that depends on what a
document holds:

  first_states(seed, docs, config)  each of `docs` documents' first state,
                                    made from the seed alone, in the form the
                                    kind keeps it
  first_writes(state)               the updates that make a first state, each
                                    with the client that wrote it, in the
                                    order the log holds them (several updates
                                    of several clients, with a delete set, are
                                    allowed)
  first_view(state)                 the view a first state makes, which the
                                    documents at rest are compared with
  first_in_row(config, arena)       what a first state takes of a document's
                                    fullest row, in the arena's own unit
                                    (`lib/room.py`: units or entries)
  edited(document)                  the shared type a client edits, in a `Doc`
                                    of the program
  view(document)                    what is compared, read from a `Doc` of the
                                    program (the server's, each client's)
  device_view(served, name)         the same, read from the device (a
                                    coroutine; `lib/serve.Served` gives
                                    `on_plane` and `device_update` to read with)
  Reference(client_before)          the plain merge, which imports nothing of
                                    the program: `apply_updates(updates)` and
                                    `state_vector()`
  reference_view(reference)         what is compared, read from the reference
  VIEWS                             the views' name in the numbers compared
                                    (`client_<VIEWS>_differing`, ...)
  CHECKS, checks(first, log, references, only_appends)
                                    the kind's own numbers compared, each a
                                    count with the limit 0

The device's view of a document that the plane cannot materialise (a tree)
is read through the plane's joiner serve: `Served.device_update`, decoded by
the kind's own reference.
"""

from __future__ import annotations

import importlib.util
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT = "text"


def name_of(config: dict) -> str:
    return config.get("document", DEFAULT)


def path_of(name: str, bench_dir: str = BENCH) -> str:
    return os.path.join(bench_dir, "documents", name + ".py")


def load(name: str, bench_dir: str = BENCH):
    """The module bench/documents/<name>.py."""
    path = path_of(name, bench_dir)
    if not os.path.isfile(path):
        raise FileNotFoundError(f"document kind {name!r} has no file at {path}")
    spec = importlib.util.spec_from_file_location("bench_document_" + name.replace("-", "_").replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
