"""The system under test's configuration, built from a configuration file."""
from __future__ import annotations

import dataclasses


def _replace(obj, overrides: dict):
    kw = {}
    for k, v in overrides.items():
        cur = getattr(obj, k)
        kw[k] = _replace(cur, v) if dataclasses.is_dataclass(cur) else v
    return dataclasses.replace(obj, **kw)


def program_config(fam, config: dict):
    """The registry's config for ``config["arch"]`` with ``overrides``
    applied (nested dataclasses take nested dicts), checked by the model
    family `fam` against the file's ``model`` block."""
    from repro.configs import get_config
    cfg = _replace(get_config(config["arch"]), config["overrides"])
    fam.check_config(cfg, config["model"])
    return cfg
