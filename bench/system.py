"""The system under test's configuration, built from a configuration file."""
from __future__ import annotations

import dataclasses


def _replace(obj, overrides: dict):
    kw = {}
    for k, v in overrides.items():
        cur = getattr(obj, k)
        kw[k] = _replace(cur, v) if dataclasses.is_dataclass(cur) else v
    return dataclasses.replace(obj, **kw)


def program_config(config: dict):
    """The registry's config for ``config["arch"]`` with ``overrides``
    applied (nested dataclasses take nested dicts), checked against the
    file's ``model`` block."""
    from repro.configs import get_config

    from bench.compare import check_config
    cfg = _replace(get_config(config["arch"]), config["overrides"])
    check_config(cfg, config["model"])
    return cfg
