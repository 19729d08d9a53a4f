"""Shared helper for the paper-figure modules."""
from __future__ import annotations

import json
import os

ARTIFACTS = os.path.join(os.path.dirname(__file__), "artifacts")


def save_artifact(name: str, payload: dict) -> str:
    os.makedirs(ARTIFACTS, exist_ok=True)
    path = os.path.join(ARTIFACTS, name + ".json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=float)
    return path
