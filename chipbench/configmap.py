"""A configuration file, in the published config's key names, mapped
onto the program through the file of its family.

The family is ``families/<model_type>.py`` (see ``families/__init__``):
it maps, fixes or folds each of the file's own keys and refuses any
other.  What every family shares lives here: the lookup, the
bookkeeping keys, and the rule for the served dtype.  A file never runs
as something other than what it states.
"""

from __future__ import annotations

import importlib
import os

FAMILIES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "families")
BOOKKEEPING = ("source", "deployment", "reduced", "assumed", "folded",
               "init", "serve", "train", "reference", "limits",
               "max_position_embeddings", "name")
SHARED = ("model_type", "torch_dtype") + BOOKKEEPING
DTYPE = ("bfloat16", "float32")


def families() -> list:
    """The model types that have a family file."""
    return sorted(f[:-3] for f in os.listdir(FAMILIES)
                  if f.endswith(".py") and f != "__init__.py")


def family(c: dict):
    """The family module of file ``c``, by its ``model_type``."""
    present = families()
    if c.get("model_type") not in present:
        raise ValueError(f"model_type {c.get('model_type')!r}: no family "
                         f"file; the families present are {present}")
    return importlib.import_module(f"families.{c['model_type']}")


def dtype(c: dict):
    import jax.numpy as jnp
    return jnp.dtype(c.get("torch_dtype", "bfloat16"))


def check(c: dict):
    """Refuse a file with a key or a value that cannot be run as
    stated; returns its family."""
    fam = family(c)
    if c.get("torch_dtype", "bfloat16") not in DTYPE:
        raise ValueError(f"torch_dtype {c['torch_dtype']!r}: served in "
                         f"{DTYPE}")
    fam.check({k: v for k, v in c.items() if k not in SHARED})
    return fam


def model_config(c: dict):
    """The program's configuration for file ``c``."""
    return check(c).model_config(c)


def shape(c: dict):
    """The work object of file ``c`` that the per-layer readers count
    with."""
    return check(c).shape(c)
