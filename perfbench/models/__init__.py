"""A configuration's own model code, found by the file's ``model_type``.

``perfbench/models/<model_type>.py`` gives, from the configuration file alone:

``program_config(config)``   the program's model config, as ``build_app``
    takes it from ``PRESETS``;
``reference_logits(params, tokens, config, *, positions)``   the plain
    reference — float32, ``highest`` — as ``(logits [len(positions), vocab],
    margins [len(positions)])``; a margin is the position's smallest routing
    margin over the layers, ``inf`` where the model routes nothing
    (``correct.py`` leaves positions with a small margin out);
``control_logits(params, tokens, config, *, positions)``   the same in the
    nearest precision below the configuration's, for ``perfbench/control.py``
    to put in the program's place: it has to come out as not correct. No
    benchmark run calls it;
``param_counts(config)``, ``kv_bytes_per_token(config)``,
``decode_step_stream_bytes(config, *, live_kv_tokens, ctx)``,
``attention_stream_bytes(config, *, kv_tokens)``   the yardstick's counts;
    both token counts are tokens on distinct physical pages (``live_kv.py``);
    ``ctx`` is the readers' ``Context``, so that a model whose step touches
    only some of its experts can count them from a program counter;
``WIDTH_KEYS``   the keys of this ``model_type`` that are widths: they may
    never stand in ``reduced``.

The harness (``server.py``, ``correct.py``, the readers) calls these and
nothing model-specific, so an architecture lands with files alone: this
module, its configuration under ``configs/`` and its entries in
BENCHMARK.json.
"""

from __future__ import annotations

import importlib
from types import ModuleType


def adapter(config: dict) -> ModuleType:
    model_type = config.get("model_type")
    if not model_type:
        raise KeyError("the configuration file has no `model_type`: it names the "
                       "module under perfbench/models/ that builds and counts the model")
    try:
        return importlib.import_module(f"{__name__}.{model_type}")
    except ModuleNotFoundError as e:
        if e.name != f"{__name__}.{model_type}":
            raise
        raise KeyError(f"model_type {model_type!r} has no module "
                       f"perfbench/models/{model_type}.py") from e
