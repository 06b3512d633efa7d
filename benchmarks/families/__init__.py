"""The one seam between the harness and a model: a configuration's family.

A configuration's file names its family (``"family": "<name>"``, required),
and ``load`` imports ``benchmarks/families/<name>/`` as a package. Nothing
else in the harness knows a model: no file outside a family names a weight
matrix or a configuration key of the model, or imports a reference. A new
architecture's cell is a new directory here, a configuration, a traffic mix
and entries in ``BENCHMARK.json``.

What a family gives, for a configuration ``conf`` (``REQUIRED``; importing
the package must not import JAX, since ``run.py``'s readers call the counts):

- ``model_config(conf, server)`` -> the program's ``ModelConfig``;
- ``make_planes(conf, seed)`` -> the weights on the device, one jitted
  program from the seed; ``wrap_planes(planes, conf)`` -> the parameter tree
  ``Engine`` takes. Only a family that serves ``tp > 1`` (``SHARDING``):
  ``make_sharded_params(conf, cfg, n_tp, seed)`` -> (params, mesh) and
  ``planes_of(params, conf)`` -> the planes its reference reads;
- ``compare(planes, conf, samples, stand_ins)`` -> the gaps behind
  ``correct``, by its own plain reference, which imports nothing of the
  program; ``stand_ins`` names those to put in the program's place
  (``control``: the nearest precision below the configuration's,
  ``witness``: the configuration's own);
- the counts, each what the mathematics needs, whatever implements it:
  ``flops_per_token(conf, context)``, ``plane_bytes_per_launch(conf, rows)``,
  ``kv_read_bytes(conf, context)`` (keys and values one row's step reads at
  that context, all layers), ``launch_least_seconds(conf, rows, peaks)``,
  ``resident_bytes(conf)``;
- ``rehearsal(conf)`` -> [(name, jitted function, arguments as
  ``ShapeDtypeStruct``s, static keywords)]: the programs
  ``rehearse_compile.py`` lowers for a described chip.
"""

from __future__ import annotations

import importlib
import os

HERE = os.path.dirname(os.path.abspath(__file__))
REQUIRED = ("model_config", "make_planes", "wrap_planes", "compare",
            "flops_per_token", "plane_bytes_per_launch", "kv_read_bytes",
            "launch_least_seconds", "resident_bytes", "rehearsal")
SHARDING = ("make_sharded_params", "planes_of")


def load(conf: dict):
    """The family a configuration names. ``ValueError``, in one line, for a
    configuration that names none or names one that is not there, for a
    family that lacks a function, and for ``tp > 1`` on one that cannot shard."""
    who, name = conf.get("name"), conf.get("family")
    if not (isinstance(name, str) and name.isidentifier()
            and os.path.exists(os.path.join(HERE, name, "__init__.py"))):
        raise ValueError(
            f'configuration {who!r} needs a "family" key that names a '
            f"directory under benchmarks/families (found {name!r})")
    mod = importlib.import_module(f"{__name__}.{name}")
    lacks = [f for f in REQUIRED if not callable(getattr(mod, f, None))]
    if lacks:
        raise ValueError(f"family {name!r} lacks {', '.join(lacks)}")
    tp = int(conf.get("tp", 1))
    if tp > 1 and not all(callable(getattr(mod, f, None)) for f in SHARDING):
        raise ValueError(
            f'configuration {who!r} has "tp": {tp}, and its family {name!r} '
            f"cannot shard (no {' / '.join(SHARDING)})")
    return mod
