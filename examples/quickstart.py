"""Quickstart: build a group-gated MoE (HL-GGN), run a forward pass, and
inspect the two-stage routing (paper eq. 5-7).

    PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_config
from repro.core.gating import group_gate_probs
from repro.core.hardware import PROFILES, DeviceState
from repro.core.selection import end_mask_for
from repro.models.model import build_model, make_dummy_batch
from repro.launch.cache import enable_compile_cache


def main():
    enable_compile_cache()
    # A reduced qwen3-moe (the HL-GGN flagship arch: 8 experts in 4 groups here)
    cfg = smoke_config(get_config("qwen3-moe-235b-a22b"))
    print(f"arch={cfg.name}  experts={cfg.moe.num_experts} "
          f"groups={cfg.moe.num_groups} top_k={cfg.moe.top_k}")

    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_dummy_batch(cfg, jax.random.PRNGKey(1), batch=2, seq=64)
    logits, aux = model.train_logits(params, batch)
    # scalar losses only — aux also carries the measured per-expert/group
    # routing fractions ([E]/[K]) that feed the serving engines' priorities
    scalars = {k: float(v) for k, v in aux.items() if np.ndim(v) == 0}
    print(f"logits {logits.shape}  aux={scalars}")

    # Peek at the two-stage gate on the embedding of the first tokens.
    x = params["embed"][jnp.asarray(batch["tokens"])].reshape(-1, cfg.d_model)
    gate_params = jax.tree.map(lambda l: l[0], params["blocks"]["pos0"]["moe"]["gate"])
    probs, p_group, _ = group_gate_probs(gate_params, x[:8].astype(jnp.float32), cfg.moe)
    print("stage-1 group probs (first token):", np.round(np.asarray(p_group[0]), 3))
    print("combined expert probs sum:", float(probs.sum(-1)[0]))

    # Hardware-aware local expert selection (eq. 2-4) for a phone-class end
    mask = end_mask_for(
        PROFILES["phone-soc"], DeviceState(mem_free=0.8),
        cfg.d_model, cfg.moe.d_ff_expert,
        cfg.moe.num_experts, cfg.moe.num_groups,
    )
    print(f"end-tier expert mask (≤40% cap): {mask.astype(int)} "
          f"({mask.sum()}/{cfg.moe.num_experts} experts local)")

    # Masked routing: excluded experts get exactly zero probability
    probs_m, _, _ = group_gate_probs(
        gate_params, x[:8].astype(jnp.float32), cfg.moe, jnp.asarray(mask)
    )
    print("masked expert probs (token 0):", np.round(np.asarray(probs_m[0]), 3))


if __name__ == "__main__":
    main()
