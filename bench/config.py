"""A configuration file under ``bench/configs/``, read into the shapes
that the harness, ``flops.py`` and ``reference.py`` use.

The file holds the published ``config.json`` keys with the cuts applied
(``reduced`` names them), an ``arch`` block that says in one vocabulary
what the published keys leave to the model's code, and a ``serving``
block with the engine's sizes.  ``arch["family"]`` names the model
family: ``bench/families/<family>.py`` under the run's root, which reads
the rest of the file (see ``bench/families/__init__.py``).  A new
configuration is a new file, and a new architecture one family file
more; nothing here names either.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import sys
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType

ROOT = Path(__file__).resolve().parents[1]


@dataclass(frozen=True)
class Shapes:
    """The sizes every family has.  A family may return a frozen
    subclass with fields of its own."""
    name: str
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    gated: bool
    activation: str
    qkv_bias: bool
    tie_embeddings: bool
    dtype: str
    max_batch: int
    max_seq: int
    page_tokens: int
    pool_pages: int
    #: the family module's file; ``family(s)`` loads it
    family: str


def family_file(name: str, root: Path = ROOT) -> Path:
    return Path(root).resolve() / "bench" / "families" / f"{name}.py"


@functools.cache
def load_family(path: str) -> ModuleType:
    """The family module in the file ``path``, loaded once per process."""
    if not Path(path).is_file():
        raise FileNotFoundError(f"no model family file {path}")
    name = f"bench_family:{path}"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod               # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def family(s: Shapes) -> ModuleType:
    """The family that reads, draws, runs and counts ``s``."""
    return load_family(s.family)


def shapes_from(raw: dict, root: Path = ROOT) -> Shapes:
    """The configuration ``raw`` read by the family it names."""
    name = raw.get("arch", {}).get("family")
    if not name:
        raise ValueError(f"{raw.get('name')}: the arch block names no family;"
                         f" a family is a file {family_file('<family>', root)}")
    path = str(family_file(name, root))
    return dataclasses.replace(load_family(path).shapes(raw), family=path)
