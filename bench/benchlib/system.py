"""Everything the benchmark knows of the system under test: how to build
its model configuration and its serving engine from a cell, and which of
its counters and tables it reads.  Nothing here changes what the system
does; the engine gets only what the cell's deployment states, every other
option stays at its default."""

from __future__ import annotations

from typing import Dict, List

import numpy as np


def model_config(conf: Dict):
    """The system's ``ModelConfig`` for a configuration file's sizes: every
    top-level key that names a field of ``ModelConfig`` (``layer_pattern``
    and ``moe`` given as JSON objects), every other field at its default."""
    import dataclasses

    from repro.configs.base import LayerSpec, ModelConfig, MoEConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {k: v for k, v in conf.items() if k in fields}
    kw["layer_pattern"] = tuple(LayerSpec(**s) for s in conf["layer_pattern"])
    if conf.get("moe") is not None:
        kw["moe"] = MoEConfig(**conf["moe"])
    return ModelConfig(**kw)


def build_model(cfg):
    from repro.models.model import build_model as build

    return build(cfg)


def param_shapes(model, key):
    import jax

    return jax.eval_shape(model.init, key)


def engine_class(name: str):
    """A serving engine of the system, by its name in ``repro.serving``."""
    import repro.serving as serving

    cls = getattr(serving, name, None)
    if not isinstance(cls, type):
        raise ValueError(f"repro.serving has no engine {name!r}")
    return cls


def engine_kwargs(deployment: Dict) -> Dict:
    """The cell's ``engine_args``, checked against the engine's keyword
    parameters and passed through as they are, except that a device
    profile's name (``*_profile``, or a list of them in ``*_profiles``)
    becomes that profile."""
    import inspect

    from repro.core.hardware import PROFILES

    cls = engine_class(deployment["engine"])
    params = inspect.signature(cls.__init__).parameters
    out = {}
    for k, v in deployment["engine_args"].items():
        p = params.get(k)
        if p is None or p.kind is not inspect.Parameter.KEYWORD_ONLY:
            raise ValueError(f"{deployment['engine']} takes no keyword {k!r}")
        if k.endswith("_profile"):
            v = PROFILES[v]
        elif k.endswith("_profiles"):
            v = [PROFILES[x] for x in v]
        out[k] = v
    return out


def build_engine(model, params, deployment: Dict, **options):
    """The engine the cell names (``engine``), built with the cell's
    ``engine_args`` and nothing else: every option the cell does not state
    stays at the engine's default, so a change of a default is measured.
    ``options`` are for the calibration tool alone."""
    cls = engine_class(deployment["engine"])
    return cls(model, params, **{**engine_kwargs(deployment), **options})


def request(index: int, prompt: np.ndarray, max_new: int):
    from repro.serving import Request

    return Request(index, prompt, max_new_tokens=max_new)


def counters(eng) -> Dict[str, object]:
    """The program's own counts, read between ticks."""
    m = {
        "prefill_chunks": int(eng.n_prefill_chunks),
        "stage_steps": int(eng.n_stage_steps),
        "host_syncs": int(eng.n_host_syncs),
        "bytes_up": int(eng.link.bytes_up),
        "traces": dict(eng.stage_trace_counts()),
    }
    return m


def idle(eng) -> bool:
    """No request in the engine and both page pools empty."""
    return (not eng.busy() and eng.end_pool.pages_in_use == 0
            and eng.cloud_pool.pages_in_use == 0)


def end_experts(eng) -> List[List[int]]:
    """Per end-tier MoE layer (pattern position major, block minor), the
    experts its end stage can route to: the applied target mask AND
    residency in the slab tables (every expert, where the end tier is not
    pooled and not masked)."""
    cfg = eng.cfg
    E = cfg.moe.num_experts
    if not eng._expert_pooled:
        mask = np.ones((E,), bool) if eng.tiers.end_mask is None else (
            np.asarray(eng.tiers.end_mask, bool))
        n = sum(1 for s in cfg.layer_pattern if s.moe) * eng.split
        return [np.nonzero(mask)[0].tolist()] * n
    emask = np.asarray(eng._emask_dev, bool)
    out = []
    for pos in sorted(eng._expert_tables):
        slot = np.asarray(eng._expert_tables[pos]["slot"])
        for b in range(eng.split):
            out.append(np.nonzero(emask & (slot[b] < eng._s_cap))[0].tolist())
    return out


def release(eng):
    """Drop the engine's device state (pools, slab store, split copies of
    the weights) before the reference runs."""
    for name in ("_end_pages", "_cloud_pages", "_slab_store", "end_params",
                 "cloud_params"):
        if hasattr(eng, name):
            setattr(eng, name, None)
