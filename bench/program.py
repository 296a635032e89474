"""The benchmark's one doorway to the program under test: a cell's
configuration file becomes the program's ``ModelConfig`` here. Drivers
import the program's entry points themselves; the reference imports
nothing of it."""
from __future__ import annotations

from typing import Any, Dict

import jax.numpy as jnp

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def model_config(config: Dict[str, Any], **overrides):
    """The configuration file's ``model`` group as the program's
    ``ModelConfig``; ``overrides`` replace keys (tests shrink sizes)."""
    from repro.models.base import ModelConfig
    kw = dict(config["model"], **overrides)
    kw["dtype"] = DTYPES[kw["dtype"]] if isinstance(kw["dtype"], str) \
        else kw["dtype"]
    return ModelConfig(**kw)


def leaf_name(path) -> str:
    """'blocks/attn/wq' for a params tree path (the reference's names)."""
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)
