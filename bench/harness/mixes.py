"""The general traffic generator: a mix file's ``kind`` names the driver
module ``harness/kinds/<kind>.py`` that reads its parameters, so a new
kind of traffic is a new file, and a new mix of a known kind is a data
file alone.

A driver module defines ``Driver(config, traffic, seed)``, which owns
everything of its kind: its data, its plain reference and its checks.

* ``CHECKS``: the names of the numbers ``check()`` compares, in order.
* ``data(seed)``: the data a run uses (for ``batch_join``, both layers),
  made from the seed; ``setup`` keeps it in ``geo``.
* ``reference(mode)``: the plain reference's answer over ``geo``, in
  ``mode`` ``"exact"`` (the precision the configuration states) or
  ``"f32"`` (the control, one precision below).
* ``setup(span)``: data, build and warm-up; returns the set-up's
  end-to-end readings (``{}`` where it has none).
* ``window(seconds, span)``: the measured window; keeps every answer in
  ``results`` and returns the window's end-to-end readings.
* ``attempted``, ``failed``, ``error``: the window's work counts and its
  last error.
* ``layer_inputs()``: what the per-layer readers read besides the trace.
* ``release()``: drops the program's state.
* ``check()``: ``{name: (value, limit)}`` for each name of ``CHECKS``,
  comparing ``results`` with ``reference("exact")``.

``bench/control.py`` sets ``geo = data(seed)`` and ``results =
[reference(mode)]`` and calls ``check()``, so the control runs on the
data the cell runs. A new kind adds its driver as
``harness/kinds/<kind>.py``, and its data generator and reference as new
modules beside ``harness/reference.py``; no existing file changes.
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
