"""A configuration file -> the program's ``TransformerCfg``.

The file names the program's architecture (``program.arch``) and, where it
is cut, how many layers are kept (``program.layers``).  The program's own
config for that architecture is taken with ``dataclasses.replace`` of the
depth alone, and every published size the file states is checked against
what the program will run (the pairs its family module lists), so a file
cannot describe one model while the program runs another.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

import harness


def _check(pairs):
    bad = [(k, want, got) for k, want, got in pairs if want != got]
    if bad:
        raise ValueError("the program's config departs from the file: "
                         + ", ".join(f"{k}: file {w!r}, program {g!r}"
                                     for k, w, g in bad))


def transformer_cfg(config: dict):
    """``program.reduced`` (test files only) starts from the program's
    smoke-test size of the architecture instead of the published one."""
    from repro.configs import get_config
    from repro.models.transformer import StageSpec
    cfg = get_config(config["program"]["arch"],
                     reduced=config["program"].get("reduced", False))
    layers = config["program"].get("layers")
    if layers is not None:
        (stage,) = cfg.stages
        cfg = dataclasses.replace(
            cfg, stages=(StageSpec(stage.layers,
                                   repeat=layers // len(stage.layers)),))
    dtype = jnp.dtype(config["dtype"])
    _check(harness.family(config).program_pairs(config, cfg)
           + [("dtype", dtype, jnp.dtype(cfg.param_dtype))])
    return cfg
