"""From a configuration file to the program's trainer, by dotted path:
nothing here names a model."""

import importlib
import math

from .batches import resolve_shape


def resolve(dotted):
    mod, _, attr = dotted.rpartition(".")
    return getattr(importlib.import_module(mod), attr)


def _call(spec, *args, **more):
    return resolve(spec["path"])(*args, **dict(spec.get("kwargs", {}), **more))


def build_trainer(config, traffic, seed, devices):
    """``config_factory`` -> ``trainer_builder(cfg, MeshSpec(**mesh),
    optimizer=..., seed=..., devices=...)``: the program's normal entry
    points with the options a user would pass."""
    cfg = _call(config["config_factory"])
    mesh_spec = resolve(config["mesh_spec"])(**traffic["mesh"])
    return _call(config["trainer_builder"], cfg, mesh_spec,
                 optimizer=_call(config["optimizer"]), seed=seed,
                 devices=devices)


def cell_dims(config, traffic):
    """The symbols a field's shape may use: the traffic's ``dims`` and
    ``B``, the global batch."""
    dims = dict(traffic.get("dims", {}))
    dims["B"] = traffic["batch"]
    return dims


def units_per_step(config, dims):
    return math.prod(resolve_shape(config["units_per_step"], dims))
