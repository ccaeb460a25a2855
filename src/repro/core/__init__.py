"""Core: the paper's contribution — EASGD family + communication co-design.

Exports resolve lazily (PEP 562): the numpy-only corners of core
(``compression``'s wire codecs, ``easgd_flat``, ``costmodel``) must stay
importable without paying the jax import — repro.net TCP worker processes
depend on that for sub-second startup.
"""
_EASGD = ("EASGDConfig", "sgd_update", "msgd_update", "easgd_worker_update",
          "measgd_worker_update", "center_update_from_sum",
          "center_update_from_mean", "center_update_single")
_ELASTIC = {"ElasticConfig": "ElasticConfig", "ElasticState": "ElasticState",
            "elastic_init": "init",
            "elastic_apply_gradients": "apply_gradients",
            "elastic_state_specs": "state_specs"}
_PACKING = ("ELASTIC_UPDATE_BLOCK", "Packer", "packed_apply")
_SUBMODULES = ("collectives", "compression", "costmodel", "des", "easgd",
               "easgd_flat", "elastic", "packing", "async_engine")

__all__ = _EASGD + tuple(_ELASTIC) + _PACKING + _SUBMODULES


def __getattr__(name):
    import importlib
    if name in _EASGD:
        return getattr(importlib.import_module("repro.core.easgd"), name)
    if name in _ELASTIC:
        return getattr(importlib.import_module("repro.core.elastic"),
                       _ELASTIC[name])
    if name in _PACKING:
        return getattr(importlib.import_module("repro.core.packing"), name)
    if name in _SUBMODULES:
        return importlib.import_module(f"repro.core.{name}")
    raise AttributeError(f"module 'repro.core' has no attribute '{name}'")
