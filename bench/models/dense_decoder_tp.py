"""Adapter from a dense pre-norm decoder's configuration file to the program
under test, served over a tensor-parallel mesh (the file's ``"mesh"``,
``"DxM"``: data x model).

The architecture, the weights' shapes, their values and the names the plain
reference reads are the dense decoder's (``bench/models/dense_decoder.py``).
What differs is where the weights live: :func:`make_weights` runs the dense
draw in one jitted call whose outputs are placed as the program places them
on the mesh (``launch.sharding.tree_pspecs`` over ``models.model.param_specs``:
heads, KV heads, FFN columns and vocabulary rows over the model axis), so
no device ever holds the whole model and the engine's own placement moves
nothing.  The random bits are partitionable, so each device draws only its
share, and the values equal those of an unsharded draw from the same key.
"""
from __future__ import annotations

import jax

from bench.models import dense_decoder as dense

reference_view = dense.reference_view

#: configuration name -> its ``"mesh"``; :func:`arch` records it, since the
#: harness hands :func:`make_weights` only the ``ArchConfig`` and the key
_MESH: dict[str, str] = {}


def arch(conf: dict, kernel_mode: str):
    """The program's ``ArchConfig`` for ``conf``."""
    _MESH[conf["name"]] = conf["mesh"]
    return dense.arch(conf, kernel_mode)


def engine_config(conf: dict, engine: dict, kernel_mode: str,
                  quant: str | None = None):
    from repro.serving import EngineConfig

    return EngineConfig(kernel_mode=kernel_mode, quant=quant,
                        mesh=conf["mesh"], **engine)


def shardings(cfg):
    """The program's placement of every weight on the configuration's
    mesh, as a tree of ``NamedSharding``."""
    from repro.launch.sharding import tree_pspecs
    from repro.models import model as M
    from repro.serving import MeshSpec

    return tree_pspecs(M.param_specs(cfg),
                       MeshSpec.parse(_MESH[cfg.name]).build())


def make_weights(cfg, key) -> dict:
    """Every weight from ``key``, as the dense decoder draws them, each on
    the devices the program places it on: the dense draw runs inside a jit
    whose ``out_shardings`` are :func:`shardings`, so each device makes only
    its share."""
    return jax.jit(lambda k: dense.make_weights(cfg, k),
                   out_shardings=shardings(cfg))(key)
