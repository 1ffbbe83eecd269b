"""Parts of the benchmark found by name, each a file of its own:
`benchmark/<kind>/<name>.py`.

- `entries/<name>.py` (a configuration's `entry`): the boundary to the
  system under test. `checker(cfg)` builds what the cell checks with;
  `wrap(entry)` makes a pool entry the program's input; `check(built,
  history)` drives one check; `answers(result, entry)` and
  `path_notes(result)` read a result back.
- `gens/<name>.py` (a configuration's `generator`): `entry(cfg,
  traffic, seed, position)`, one pool entry as [(key, ops)].
- `references/<name>.py` (a configuration's `model`): `check(ops,
  crashed_dropped=False)`, one key's answer; `crashed_dropped` is the
  control.
- `metrics/<name>.py` (a metric's `name`): `read(run)`.

A later cell that needs a new family brings these files and its
`BENCHMARK.json` entries; nothing here changes."""

from __future__ import annotations

import importlib.util
import os
import re

_LOADED: dict = {}


def load(root: str, kind: str, name: str):
    """The module `<root>/benchmark/<kind>/<name>.py`, loaded once a
    path."""
    path = os.path.join(root, "benchmark", kind, name + ".py")
    mod = _LOADED.get(path)
    if mod is None:
        if not os.path.exists(path):
            raise ValueError(f"no {kind} named {name!r} ({path})")
        spec = importlib.util.spec_from_file_location(
            "bench_%s_%s" % (kind, re.sub(r"\W", "_", name)), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return mod
