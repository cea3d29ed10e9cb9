"""A configuration file (``bench/configs/<name>.json``) as the program's
``ModelConfig``: the published sizes are read here and nowhere else, so a
later edit of the program's own configs cannot move the yardstick.

What belongs to one architecture lives in files of its own, found by name
under the benchmark's data directory (the parent of the configuration's
directory, recorded in the dict as ``bench_dir``):

- ``archs/<model_type>.py``: ``program_config(c)`` builds the program's
  ``ModelConfig``; optionally ``scale_rule(path, shape)`` and ``STACKED``
  for the seeded weights (:mod:`bench.weights`);
- ``refs/<reference>.py``: the plain reference and its layout, which
  :mod:`bench.counts` counts from.
"""
from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
CONFIG_DIR = BENCH / "configs"


def load_config(name: str, directory: Path = CONFIG_DIR) -> dict:
    path = directory / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no configuration file {path}")
    cfg = json.loads(path.read_text())
    cfg["name"] = name
    cfg["bench_dir"] = str(Path(directory).resolve().parent)
    return cfg


@functools.lru_cache(maxsize=None)
def _load(path: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_{Path(path).parent.name}_{Path(path).stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_module(c: dict, kind: str, name: str):
    """``<bench_dir>/<kind>/<name>.py`` of configuration ``c``, loaded by
    path once per process."""
    path = Path(c.get("bench_dir", BENCH)) / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"{c.get('name')}: no {kind} module {path}")
    return _load(str(path))


def arch(c: dict):
    """The architecture module of ``c``, by its ``model_type``."""
    return load_module(c, "archs", c["model_type"])


def reference(c: dict):
    """The reference module of ``c``, by its ``reference``."""
    return load_module(c, "refs", c["reference"])


def model_config(c: dict):
    """The program's ModelConfig for configuration dict ``c``."""
    return arch(c).program_config(c)
