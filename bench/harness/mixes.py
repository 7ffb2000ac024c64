"""The general traffic generator: a mix file's ``kind`` names the driver
module ``harness/kinds/<kind>.py`` that reads its parameters, so a new
kind of traffic is a new file, and a new mix of a known kind is a data
file alone.

A driver module defines ``Driver(config, traffic, seed)`` with
``setup(span)``, ``window(seconds, span)``, ``attempted``, ``failed``,
``error``, ``layer_inputs()``, ``release()`` and ``check()``.
"""
from __future__ import annotations

import importlib
from pathlib import Path

KINDS_DIR = Path(__file__).resolve().parent / "kinds"


def kind_path(kind: str) -> Path:
    return KINDS_DIR / f"{kind}.py"


def make(kind: str, config: dict, traffic: dict, seed: int):
    if not kind.isidentifier() or not kind_path(kind).is_file():
        raise ValueError(f"no traffic driver {kind_path(kind)}")
    module = importlib.import_module(f".kinds.{kind}", __package__)
    return module.Driver(config, traffic, seed)
