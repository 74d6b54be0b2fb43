"""The only place the benchmark reaches into the program for this block's
model: the program's own model object
(``models/cohere2_moe.py:Cohere2Moe``) at a configuration's sizes, with the
seed's weights handed in through its init hook."""

from __future__ import annotations


def flat(tree) -> dict:
    """Nested params -> ``{"layer_0/attention/query/kernel": leaf}``."""
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
    from distkeras_tpu.models.cohere2_moe import Cohere2MoeConfig

    return Cohere2MoeConfig(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"], head_dim=sizes["head_dim"],
        expert_dim=sizes["intermediate_size"],
        num_experts=sizes["num_router_outputs"],
        experts_per_token=sizes["num_experts_per_tok"],
        held_experts=(sizes.get("first_expert", 0), sizes["num_experts"]),
        num_shared_experts=sizes["num_shared_experts"],
        layer_types=tuple(sizes["layer_types"]),
        sliding_window=sizes["sliding_window"],
        rope_theta=float(sizes["rope_theta"]),
        layer_norm_eps=float(sizes["layer_norm_eps"]),
        logit_scale=float(sizes["logit_scale"]),
        max_seq_len=sizes["max_seq_len"])


def program_model(config: dict, seed: int, reference):
    """The program's own model object at the configuration's sizes, with
    its init hook handing out the seed's weights (made by ``reference``,
    this directory's plain reference, not by the program). Refuses a model
    whose parameter tree is not the reference's, leaf for leaf."""
    import jax

    from distkeras_tpu.models import cohere2_moe
    from distkeras_tpu.models.core import Model

    sizes = config["model"]["config"]
    base = cohere2_moe._make(program_config(sizes), config["model"]["seq_len"],
                             config["name"])
    want = reference.weight_spec(sizes)
    have = {k: tuple(v.shape) for k, v in flat(jax.eval_shape(
        base.init, jax.random.PRNGKey(0))["params"]).items()}
    if have != want:
        odd = sorted(set(have.items()) ^ set(want.items()))[:6]
        raise SystemExit(f"chipbench: the program's parameter tree is not "
                         f"the reference's: {odd}")

    def init_fn(_rng):
        return {"params": _nested(reference.make_weights(sizes, seed))}

    model = Model(init_fn, base.apply, name=base.name,
                  input_shape=base.input_shape, output_dim=base.output_dim,
                  flops_per_example=base.flops_per_example)
    model.config = base.config
    model.flax_module = base.flax_module
    model.boxed_init = base.boxed_init
    return model
