"""Architecture families: everything the benchmark knows about one kind of
model, one file each.

A configuration file names its family under ``"family"``; the family's
file is ``chipbench/families/<family>.py``. The generic code (the
harness, the plain reference and the cost counters) reaches the
architecture only through the functions below, so a new architecture
comes in as new files alone: its configuration, its family file, its
cell and its readers. The family is also the only place that knows the
program's executor from the inside: how to warm it and what it holds on
the device.

Each family provides:

``dims(config) -> dict``
    The sizes the benchmark computes with, read from the configuration
    file under the source's own key names. It holds at least ``family``
    (the configuration's own ``"family"`` value), ``n_layers``,
    ``d_model``, ``vocab_size``, ``norm_eps`` and ``tie_embeddings``, all
    hashable, so that the reference can pass them to a jitted function
    as a static argument (``reference.frozen``).
``model_config(config)``
    The program's ``ModelConfig`` for the configuration.
``layer_weights(key, layer, dims, dtype)``, ``top_weights(key, dims, dtype)``
    One layer's weights and the top-level ones (``embed``, ``final_norm``
    and, unless ``tie_embeddings``, ``head``), from the key of
    ``weights.seed_key(seed)``, in ``dtype``. A leaf's key depends only on
    the seed, the layer and the leaf's path (``weights.leaf_key``), so
    that the reference can remake one layer alone; ``layer`` may be
    traced.
``make_params(seed, dims, device, dtype)``
    The whole tree that the program reads, made on ``device`` in one
    jitted call, with the same values as the two functions above. A
    family whose layers differ by index (leading dense layers, experts)
    builds its own tree.
``reference_layer(h, lw, n_valid, layer, dims, quant)``
    One decoder layer of the plain float32 forward pass over one padded
    sequence ``h [T, d_model]`` whose first ``n_valid`` rows are real,
    with ``lw`` the layer's weights in float32. Every matrix product goes
    through ``reference.mm(x, w, quant)``, so that ``quant`` (the fp8
    control) covers the whole family.
``matmul_params_per_token(dims)``
    The weights one token multiplies through, summed over the layers (the
    active ones, for a family with experts); the output head is counted
    apart (``costs.head_flops``).
``prefill_attn_flops(dims, chunk, ctx)``, ``prefill_attn_bytes(dims, chunk, ctx)``
    Attention of ``chunk`` new tokens after ``ctx`` cached ones, summed
    over the layers, over the keys each layer's queries may see.
``decode_attn_flops(dims, ctx)``, ``decode_attn_bytes(dims, ctx)``
    One query over ``ctx`` keys, its own included, summed over the layers.
``warm_phases(ex, ecfg, mix) -> {phase: (shapes, call)}``
    For one engine's executor ``ex`` under its ``EngineConfig`` ``ecfg``,
    the program shapes the traffic mix ``mix`` can reach in each phase
    (``prefill``, ``decode``, ``extract_kv``, ``inject_kv``) and the call
    that compiles one shape (``call(*shape)`` for a tuple). The calls
    write only the executor's trash page and read one logits row back the
    way the executor does. The harness picks the phases each engine's
    role runs, and times and counts them.
``device_state(ex) -> tuple``
    The executor's device arrays besides the weights, which the harness
    blocks on around the profiled span and deletes before the reference
    runs.

``paged.py`` holds the last two for families served by the program's
paged executor; it is a helper, not a family.

``load`` looks first under the checkout a run was given, then here; a
configuration without a family, or a family without a file, is an error.
"""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path
from types import ModuleType
from typing import Optional

HERE = Path(__file__).resolve().parent


def load(name: str, root: Optional[Path] = None) -> ModuleType:
    """The family ``name``: ``<root>/chipbench/families/<name>.py`` if that
    exists, else this package's own ``<name>.py``. A family is loaded once
    per process, as the module ``chipbench.families.<name>``."""
    if not name.isidentifier():
        raise ValueError(f"architecture family {name!r} is not a module name")
    mod_name = f"{__name__}.{name}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    paths = ([Path(root) / "chipbench" / "families" / f"{name}.py"]
             if root is not None else []) + [HERE / f"{name}.py"]
    for path in paths:
        if path.is_file():
            spec = importlib.util.spec_from_file_location(mod_name, path)
            mod = importlib.util.module_from_spec(spec)
            sys.modules[mod_name] = mod
            try:
                spec.loader.exec_module(mod)
            except BaseException:
                del sys.modules[mod_name]
                raise
            setattr(sys.modules[__name__], name, mod)
            return mod
    raise FileNotFoundError(
        f"no file for architecture family {name!r}: looked for "
        + " and ".join(str(p) for p in paths))


def for_config(config: dict, path: Path,
               root: Optional[Path] = None) -> ModuleType:
    """The family that the configuration file at ``path`` names."""
    if "family" not in config:
        raise ValueError(f"{path} names no architecture family: add "
                         f"\"family\", the name of a file "
                         f"chipbench/families/<family>.py")
    return load(config["family"], root)


def of(dims: dict) -> ModuleType:
    """The family whose ``dims`` these are (loaded by ``for_config``)."""
    return load(dims["family"])
