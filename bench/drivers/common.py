"""What the drivers share: the program's model from a configuration file."""

from __future__ import annotations

import dataclasses
import importlib


def program_model(config):
    """``(family module, config object)`` of the program for ``config``: the
    file's sizes go to the dataclass its ``program_config`` names."""
    from repro.models.registry import get_family

    mod, name = config["program_config"].split(":")
    cls = getattr(importlib.import_module(mod), name)
    fields = {f.name for f in dataclasses.fields(cls)}
    return get_family(config["family"]), cls(**{k: v for k, v in config.items()
                                                 if k in fields})
