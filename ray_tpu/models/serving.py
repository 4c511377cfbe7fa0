"""What a model family offers the serving engine: one record of
functions over one cache contract.

``llm/engine.py`` schedules requests onto slots and pages; it never
learns what a KV page looks like, which axes shard, or what one step
computes. It finds all of that here, from the config it is handed
(:func:`model_for`), and ``llm/serve.py`` finds config and family from a
name (:func:`named`). A family's module builds a :class:`ServingModel`
and registers it when imported; nothing under ``models/`` imports
``llm/``. Adding a serving architecture is a model file and its name in
``FAMILIES``.

The cache contract. A cache is a pytree of device arrays that only the
family's own functions look into. The engine owns which PHYSICAL PAGE
(0 .. num_pages - 1) belongs to whom and hands the step a page table
``tables [slots, max_seq // page_size]``; page 0 is the scratch page
that every invalid write must be routed to. Exported pages travel as
FRAMES: one host array with the pages along axis 2, so the engine can
cut and pad a run of pages without knowing the other axes.
"""

from __future__ import annotations

import importlib
from dataclasses import dataclass
from typing import Any, Callable, Dict, Tuple

# Modules of this package that register a ServingModel on import.
FAMILIES = ("llama",)


@dataclass(frozen=True)
class ServingModel:
    config_type: type
    # name -> config: the family's own dict, looked into at call time (a
    # deployment may add its config after import).
    configs: Dict[str, Any]
    # (key, cfg) -> (params, logical axes); () -> logical axes
    init_params: Callable
    param_axes: Callable
    # (cfg, tp): ValueError unless tp divides what the family shards
    check_shardable: Callable
    # (cfg, num_pages, page_size) -> cache; its logical axes, same tree
    init_cache: Callable
    cache_axes: Any
    # (params, cache, tables, tokens [B], pos [B], chunk, cfg, page_size,
    # rules) -> (logits [B, vocab], chunk logits [vocab] or None, cache).
    # chunk is None or (tokens [C], slot, p0, n_valid): one prompt chunk
    # for ``slot`` from position p0, its first n_valid tokens real. A row
    # at pos >= cfg.max_seq is parked: it writes nothing and its logits
    # are ignored.
    step: Callable
    # (cache, src [N], dst [N]) -> cache; (cache, dst [N], frames) -> cache
    copy_pages: Callable
    write_pages: Callable
    # (cache, idx [N]) -> host frames; (cache, frames): ValueError unless
    # the frames are pages of such a cache
    read_pages: Callable
    check_frames: Callable


_MODELS: Dict[type, ServingModel] = {}


def register(model: ServingModel) -> None:
    _MODELS[model.config_type] = model


def model_for(cfg) -> ServingModel:
    """The serving record of ``cfg``'s family."""
    try:
        return _MODELS[type(cfg)]
    except KeyError:
        raise TypeError(
            f"no serving model is registered for {type(cfg).__name__}"
        ) from None


def named(name: str) -> Tuple[Any, ServingModel]:
    """(config, serving record) registered under ``name``."""
    for family in FAMILIES:
        importlib.import_module(f".{family}", __package__)
    for model in _MODELS.values():
        if name in model.configs:
            return model.configs[name], model
    raise KeyError(name)
