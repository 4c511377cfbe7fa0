"""What a model family offers the serving engine: one record of
functions over one cache contract.

``llm/engine.py`` schedules requests onto slots and pages; it never
learns what a KV page looks like, which axes shard, or what one step
computes. It finds all of that here, from the config it is handed
(:func:`model_for`), and ``llm/serve.py`` finds config and family from a
name (:func:`named`). A family's module builds a :class:`ServingModel`
and registers it when imported; nothing under ``models/`` imports
``llm/``, and no family imports another.

Adding a serving architecture is a model file and its name in
``FAMILIES``. The file TAKES from ``models/step.py`` the page pool
(``init_pool`` with its count of attention layers, ``PAGED_KV_AXES``,
``PAGE_FUNCTIONS`` to register as they are), the rows of a step
(``step_rows``: who is parked, how much of the chunk is real, the
kernel's row metadata, which row is the chunk's logits), ``attend`` for
its paged layers, ``logits_of`` round its own head, ``state_rows`` for a
count, ``carried_conv`` if it carries a window; from ``models/common.py``
the seeded ``draw`` / ``bulk_key``, the norms and the rotary step on flat
lanes; from ``models/moe.py`` the expert layer. It WRITES its config, its
parameters' shapes, its mixer(s) on a step's rows (with ``ops/
slot_stream.py step_plan`` inside its own scan scope, if a kernel streams
its state), its head, ``attach`` / ``reset`` of its state a slot, and the
loop over its layers under its own scope names.

The cache contract. A cache is a pytree of device arrays that only the
family's own functions look into. The engine owns which PHYSICAL PAGE
(0 .. num_pages - 1) belongs to whom and hands the step a page table
``tables [slots, max_seq // page_size]``; page 0 is the scratch page
that every invalid write must be routed to. Exported pages travel as
FRAMES: one host array with the pages along axis 2, so the engine can
cut and pad a run of pages without knowing the other axes.

Per-slot state. A family may keep, beside its pages, a state of fixed
size for each of the engine's SLOTS (a short convolution's last inputs,
a recurrence's carry) and says so with a :class:`SlotState`. The state
lives in the same cache tree, so the step's signature is the same; the
engine still looks into neither and keeps four promises about it:

* it zeroes a slot's state (``reset``) when it admits a request into the
  slot, before the request's first chunk is dispatched;
* a parked row (``pos >= max_seq``) and the tail of a chunk past
  ``n_valid`` leave the state as it was: the step sees to that;
* a prompt's chunks reach one slot in order, each from where the last
  ended, so the state the step leaves is the state the next chunk (and
  then the slot's decode row) starts from;
* pages never travel without the state that goes with them. A page
  holds what attention needs of a token; the state after that token is
  not in it. So for such a family the engine TAKES NO PREFIX HIT (it
  keeps no radix index: every prompt prefills from position 0, and the
  partial-page copy-on-write of ``copy_pages`` is never asked for), a
  session is exported as its transcript alone and re-prefills where it
  is imported, and an import that comes WITH page frames is refused with
  :class:`SlotStateError`. Keeping a snapshot of the state at each
  cached page boundary would lift all three; nothing here does yet.

A family without slot state (``slot_state=None``, the default) pays
nothing: no program, no argument and no branch of its step changes.
"""

from __future__ import annotations

import importlib
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

# Modules of this package that register a ServingModel on import.
FAMILIES = ("llama", "lfm2", "solar", "granite")


class SlotStateError(ValueError):
    """Pages were offered without the per-slot state that belongs to
    them: the family keeps state a slot beside its pages, and a page
    alone cannot say what that state was at its boundary."""


@dataclass(frozen=True)
class SlotState:
    """What a family with a fixed-size state per slot adds to its cache
    contract (the module docstring says what the engine does with it)."""
    # (cfg, cache, num_slots) -> cache: the pages' tree with the state of
    # num_slots slots beside them, all zero; ``cache_axes`` describes
    # this whole tree
    attach: Callable
    # (cache, slots [N] int32) -> cache: those slots' state zeroed
    reset: Callable


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
    # None: the cache is pages alone
    slot_state: Optional[SlotState] = None
    # Names of whole-number counts one step returns beside its logits,
    # as a fourth result [len(step_counters)] int32 (the engine adds
    # them up under these names and fetches them with the step's tokens).
    # Empty: the step returns three results, as above.
    step_counters: Tuple[str, ...] = ()
    # True: a row's result repeats bit for bit only within ONE compiled
    # program (the compiler's matmuls round a row differently at other
    # row counts, and the family amplifies a differing bit: a router that
    # picks another expert). The engine then runs the fused program on
    # every step, with an empty chunk (n_valid 0, which the step must
    # take: nothing written, nothing read) when no prompt is pending, so
    # a greedy request gives the same tokens whatever else is in flight.
    one_program: bool = False


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
