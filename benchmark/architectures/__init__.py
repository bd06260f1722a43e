"""An architecture is a module found by name: everything the harness has
to know of a block, and nothing of the program.

A configuration's file may carry ``"architecture": "<name>"``; a file
without the key is a ``qwen2``. The harness loads
``architectures/<name>.py`` from the benchmark's own directory (a test
hands ``of`` another), and the module has four members:

- ``tree_shapes(cfg)``: the served tree's leaf shapes as a nested dict:
  top-level leaves, and any number of groups of leaves stacked on a
  leading layer axis (``{"embed": (V, H), "layers": {"ln1": (L, H), ...}}``);
- ``init_rule(name)``: how ``weights.py`` makes the leaf of that name, one
  of ``weights.RULES``: no leaf is left at a value with which a reference
  could drop it and still agree;
- ``forward_logits(params, cfg, tokens, positions, control=None)``: the
  plain reference: float32 logits ``[len(positions), vocab]`` of one full
  forward pass over one sequence, under
  ``jax.default_matmul_precision("highest")``, a layer at a time raised
  from the served tree, attention in query blocks, the head in slices;
- ``CONTROLS``: the lower precisions that reference can switch on through
  ``control``, each of which ``correct.py``'s comparison has to refuse.
"""

from __future__ import annotations

import functools
import importlib.util
import re
from pathlib import Path
from types import ModuleType
from typing import Any, Dict

HERE = Path(__file__).resolve().parent.parent
DEFAULT = "qwen2"
MEMBERS = ("tree_shapes", "init_rule", "forward_logits", "CONTROLS")
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@functools.lru_cache(maxsize=None)
def _load(name: str, directory: Path) -> ModuleType:
    path = directory / "architectures" / f"{name}.py"
    if not _NAME.match(name) or not path.is_file():
        raise RuntimeError(f"no architecture {name!r}: {path} is not there")
    spec = importlib.util.spec_from_file_location(f"_architecture_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    missing = [m for m in MEMBERS if not hasattr(module, m)]
    if missing:
        raise RuntimeError(f"architecture {name!r} ({path}) lacks {missing}")
    return module


def of(cfg: Dict[str, Any], directory: Path = HERE) -> ModuleType:
    """The module of the configuration's architecture."""
    return _load(str(cfg.get("architecture", DEFAULT)), Path(directory).resolve())
