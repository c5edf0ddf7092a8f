"""Traffic mixes: one JSON file of parameters per mix, one driver per kind.

``bench/traffic/<mix>.json`` names its driver with ``"kind"``; the driver
is the module ``bench/traffic/<kind>.py``. A new mix of a known kind is a
new JSON file and nothing else.
"""
from __future__ import annotations

import importlib
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


def load_mix(name: str, directory: Path = HERE) -> dict:
    with open(Path(directory) / f"{name}.json") as f:
        return json.load(f)


def driver(kind: str):
    return importlib.import_module(f"bench.traffic.{kind}")
