"""The one place that turns a configuration's file into the program's own
configuration object, through the program's public preset."""

from __future__ import annotations

import importlib

from chipbench.lib import BenchError


def program_config(config: dict):
    """``(family module, configuration object)``: the preset with the keys the
    file lists as reduced and the program's own switches. Every number the file
    states is checked against what the preset gives."""
    import jax.numpy as jnp

    family = importlib.import_module(f"accelerate_tpu.models.{config['family']}")
    cls_name, preset = config["preset"].split(".")
    keys = {k: config[k] for k in config["reduced"]}
    keys.update(config.get("program_keys", {}))
    keys["param_dtype"] = jnp.dtype(config["precision"]["parameters"])
    built = getattr(getattr(family, cls_name), preset)(**keys)
    for key, value in config.items():
        if isinstance(value, (int, float)) and not isinstance(value, bool) and hasattr(built, key):
            if getattr(built, key) != value:
                raise BenchError(
                    f"chipbench: {config['name']}: the preset gives {key}="
                    f"{getattr(built, key)}, the configuration's file states {value}"
                )
    return family, built
