"""The only place the benchmark reaches into the program for this block's
model: the program's own model object (``models/xing4.py:Xing4`` with
``sandwich_norm``) at a configuration's sizes, with the seed's weights
handed in through its init hook."""

from __future__ import annotations

import threading
import time


def flat(tree) -> dict:
    """Nested params -> ``{"layer_0/attention/q_down": leaf}``."""
    import jax

    return {"/".join(str(getattr(k, "key", getattr(k, "name", k)))
                     for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _nested(flat: dict) -> dict:
    out: dict = {}
    for name, leaf in flat.items():
        node = out
        *parents, last = name.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return out


def program_config(sizes: dict):
    """The program's own architecture config at the benchmark's sizes."""
    from distkeras_tpu.models.xing4 import Xing4Config

    return Xing4Config(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        num_heads=sizes["num_attention_heads"],
        q_lora_rank=sizes["q_lora_rank"], kv_lora_rank=sizes["kv_lora_rank"],
        qk_nope_head_dim=sizes["qk_nope_head_dim"],
        qk_rope_head_dim=sizes["qk_rope_head_dim"],
        v_head_dim=sizes["v_head_dim"],
        num_hidden_layers=sizes["num_hidden_layers"],
        first_dense_layers=sizes["first_k_dense_replace"],
        dense_dim=sizes["intermediate_size"],
        expert_dim=sizes["moe_intermediate_size"],
        num_experts=sizes["num_router_outputs"],
        experts_per_token=sizes["num_experts_per_tok"],
        held_experts=(sizes.get("first_expert", 0), sizes["num_experts"]),
        num_shared_experts=sizes["n_shared_experts"],
        routed_scaling_factor=float(sizes["routed_scaling_factor"]),
        router_bias=False, sandwich_norm=True,
        rms_norm_eps=float(sizes["rms_norm_eps"]),
        rope_theta=float(sizes["rope_theta"]), rope_factor=1.0,
        max_seq_len=sizes["max_seq_len"])


def program_model(config: dict, seed: int, reference):
    """The program's own model object at the configuration's sizes, with
    its init hook handing out the seed's weights (made by ``reference``,
    this directory's plain reference, not by the program). Refuses a model
    whose parameter tree is not the reference's, leaf for leaf."""
    import jax

    from distkeras_tpu.models import xing4
    from distkeras_tpu.models.core import Model

    sizes = config["model"]["config"]
    base = xing4._make(program_config(sizes), config["model"]["seq_len"],
                       config["name"])
    want = reference.weight_spec(sizes)
    have = {k: tuple(v.shape) for k, v in flat(jax.eval_shape(
        base.init, jax.random.PRNGKey(0))["params"]).items()}
    if have != want:
        odd = sorted(set(have.items()) ^ set(want.items()))[:6]
        raise SystemExit(f"chipbench: the program's parameter tree is not "
                         f"the reference's: {odd}")

    def init_fn(_rng):
        weights = reference.make_weights(sizes, seed)

        def compile_reference():
            # The reference's programs, compiled on a few threads while
            # the server prefills its first wave (from a minute on, once
            # the server's own programs are compiled), wait in the compile
            # cache for the reference that runs after the window: cold,
            # they took ~1 min there, and a run of the cell has to fit the
            # benchmark's time limit.
            time.sleep(60.0)
            reference.compile_requests_ahead(
                weights, sizes, config["model"]["seq_len"], threads=3)

        threading.Thread(target=compile_reference, daemon=True).start()
        return {"params": _nested(weights)}

    model = Model(init_fn, base.apply, name=base.name,
                  input_shape=base.input_shape, output_dim=base.output_dim,
                  flops_per_example=base.flops_per_example)
    model.config = base.config
    model.flax_module = base.flax_module
    model.boxed_init = base.boxed_init
    return model
