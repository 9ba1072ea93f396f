"""A configuration file of ``configs/`` read into what every cell shares,
with the sizes that its model module reads.

The file holds the published ``config.json`` keys as they are run (each
key changed from the source is listed under ``reduced``), the engine's
settings under ``engine``, and under ``model`` the name of the module of
``models/`` that knows the architecture: its sizes, the program's
``ModelConfig``, the weight layout, the plain reference and the counts
of operations and bytes. Adding an architecture adds such a module.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import re
from pathlib import Path
from types import ModuleType
from typing import Any


def load_model(name: str) -> ModuleType:
    """The model module ``models/<name>.py``."""
    if not isinstance(name, str) or not re.fullmatch(r"[A-Za-z_][A-Za-z0-9_]*", name):
        raise ValueError(f'"model": {name!r} is not the name of a module')
    try:
        return importlib.import_module(f"models.{name}")
    except ModuleNotFoundError as e:
        if e.name not in ("models", f"models.{name}"):
            raise
        raise ValueError(f'"model": {name!r} names no module of models/') from e


@dataclasses.dataclass(frozen=True)
class Arch:
    name: str
    vocab: int
    slots: int
    max_len: int
    cache_dtype: str
    weights_dtype: str
    model: str
    sizes: Any                  # the model module's record of the sizes
    module: ModuleType = dataclasses.field(compare=False, repr=False)

    @classmethod
    def from_file(cls, path: Path) -> "Arch":
        try:
            return cls.from_dict(json.loads(Path(path).read_text()))
        except ValueError as e:
            raise ValueError(f"{path}: {e}") from e

    @classmethod
    def from_dict(cls, d: dict) -> "Arch":
        if "model" not in d:
            raise ValueError('no "model" key: it names the module of models/ '
                             'that runs this architecture')
        module = load_model(d["model"])
        eng = d["engine"]
        return cls(name=d["name"], vocab=int(d["vocab_size"]),
                   slots=int(eng["slots"]), max_len=int(eng["max_len"]),
                   cache_dtype=eng["cache_dtype"],
                   weights_dtype=eng["weights_dtype"], model=d["model"],
                   sizes=module.sizes(d), module=module)

    def model_config(self):
        """The program's ``ModelConfig`` for these sizes."""
        return self.module.model_config(self.sizes)
