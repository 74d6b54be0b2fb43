"""The only place the benchmark reaches into the program for this block's
model: the program's own model object (``models/bert.py:Bert``) at a
configuration's sizes, with the seed's weights handed in through its init
hook."""

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


def program_model(config: dict, seed: int, reference):
    """The program's own model object at the configuration's sizes, with
    its init hook handing out the seed's weights (made by ``reference``,
    this directory's plain reference, not by the program). Refuses a model
    whose parameter tree is not the reference's, leaf for leaf."""
    import jax

    from distkeras_tpu.models import bert
    from distkeras_tpu.models.core import Model

    sizes = config["model"]["config"]
    seq = config["model"]["seq_len"]
    base = bert._make(bert.BertConfig(**sizes), seq, config["name"])
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
