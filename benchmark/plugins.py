"""Generators and readers are found by name: `generators/<kind>.py`,
`readers/<reader>.py`. A later PR adds one as a new file."""

from __future__ import annotations

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load(directory: str, name: str):
    path = os.path.join(HERE, directory, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"{directory}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
