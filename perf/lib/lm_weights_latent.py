"""Seeded weights of a latent-attention language-model configuration
(`joyai-llm-flash`), made by the benchmark and handed to both sides of
`correct`: `lm_weights.py`'s scheme for the other family's layout.

The unit is one array of the REFERENCE's layout (the equations' names,
nothing stacked or fused: `perf/configs/joyai-llm-flash.reference.py`),
made on the device from the seed, the layer's number and the array's name
alone. The reference is given `weights(config, seed)`: a layer's ``op``
and ``ffn`` make themselves when asked for (the ends too), so that one
part is alive at a time. `program_params` writes the very same arrays,
one at a time, into the stacked pytree `tensorframes_tpu.models.lm` takes
as its bound argument (W1 | W3 side by side in ``w_up`` and
``shared_up``). The program's own `lm.init_params` is not used: a fault in
how the program stacks, fuses or indexes its weights is a fault `correct`
sees. The configuration is read under its published key names.
"""

import jax
import jax.numpy as jnp
import numpy as np

from . import datagen
from .lm_weights import _draw, _write

NAMES = (
    "embed", "head", "final_norm", "op_norm", "ffn_norm", "w_qa", "q_a_norm",
    "w_qb", "w_kva", "kv_a_norm", "w_kvb", "wo", "w1", "w3", "w2", "router",
    "bias", "shared_w1", "shared_w3", "shared_w2",
)
ENDS = -1  # the "layer" of embed, head and final_norm


def _std(config):
    return float(config.get("initializer_range", 0.02))


def op_shapes(config):
    """{name: (shape, scale)} of the latent attention; scale None is a
    norm's gain."""
    d, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    rq, rkv = int(config["q_lora_rank"]), int(config["kv_lora_rank"])
    dn, dr = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    dv, std = int(config["v_head_dim"]), _std(config)
    return {
        "w_qa": ((d, rq), std), "q_a_norm": ((rq,), None),
        "w_qb": ((rq, heads * (dn + dr)), float(config.get("query_out_range", std))),
        "w_kva": ((d, rkv + dr), std), "kv_a_norm": ((rkv,), None),
        "w_kvb": ((rkv, heads * (dn + dv)), std), "wo": ((heads * dv, d), std),
    }


def ffn_shapes(config, experts, held=None):
    d, std = int(config["hidden_size"]), _std(config)
    if not experts:
        f = int(config["intermediate_size"])
        return {"w1": ((d, f), std), "w3": ((d, f), std), "w2": ((f, d), std)}
    fe, e = int(config["moe_intermediate_size"]), int(config["n_routed_experts"])
    count = (held or (0, e))[1]
    out = {"router": ((d, e), std),
           "bias": ((e,), float(config.get("router_bias_range", 0.1))),
           "w1": ((count, d, fe), std), "w3": ((count, d, fe), std),
           "w2": ((count, fe, d), float(config.get("expert_out_range", std)))}
    fs = int(config.get("n_shared_experts") or 0) * fe
    if fs:
        out.update({"shared_w1": ((d, fs), std), "shared_w3": ((d, fs), std),
                    "shared_w2": ((fs, d), std)})
    return out


def shapes(config, i, held=None):
    """{group: {name: (shape, scale)}} of layer `i` (`ENDS`: the ends)."""
    d, v, std = int(config["hidden_size"]), int(config["vocab_size"]), _std(config)
    if i == ENDS:
        return {"": {"embed": ((v, d), std), "head": ((d, v), std),
                     "final_norm": ((d,), None)}}
    return {"": {"op_norm": ((d,), None), "ffn_norm": ((d,), None)},
            "op": op_shapes(config),
            "ffn": ffn_shapes(config, i >= int(config["first_k_dense_replace"]), held)}


def array(config, seed, i, name, held=None):
    """The array `name` of layer `i`: normal(0, scale), a norm's gain 1 +
    normal(0, 0.05), rounded to the configuration's dtype."""
    (shape, scale), = [
        g[name] for g in shapes(config, i, held).values() if name in g
    ]
    key = jax.random.PRNGKey(int(datagen.seed_word(seed)) & 0x7FFFFFFF)
    key = jax.random.fold_in(jax.random.fold_in(key, i + 1), NAMES.index(name))
    return _draw(key, shape, scale, jnp.dtype(config.get("dtype", "bfloat16")))


class Part:
    """A mapping of one group's arrays, each made when asked for."""

    def __init__(self, make, names):
        self.make, self.names = make, list(names)

    def keys(self):
        return list(self.names)

    def __contains__(self, name):
        return name in self.names

    def __iter__(self):
        return iter(self.names)

    def __getitem__(self, name):
        if name not in self.names:
            raise KeyError(name)
        return self.make(name)


class Layers:
    """The layers in the reference's layout: a layer is a mapping whose
    norms, ``op`` and ``ffn`` make themselves when asked for."""

    def __init__(self, config, seed, held=None):
        self.config, self.seed, self.held = config, seed, held

    def __len__(self):
        return int(self.config["num_hidden_layers"])

    def array(self, i, name):
        return array(self.config, self.seed, i, name, self.held)

    def __getitem__(self, i):
        groups = shapes(self.config, i, self.held)
        make = lambda name: self.array(i, name)

        def one(name):
            if name in groups[""]:
                return make(name)
            # what a jitted reference function takes: a plain dict, made now
            return {n: make(n) for n in groups[name]}

        return Part(one, list(groups[""]) + ["op", "ffn"])


class Weights(Part):
    """What the reference's `forward` takes: the ends and ``"layers"``,
    every array made when asked for and kept by whoever asked."""

    def __init__(self, config, seed, held=None):
        self.layers = Layers(config, seed, held)
        super().__init__(
            lambda name: self.layers if name == "layers"
            else array(config, seed, ENDS, name),
            list(shapes(config, ENDS)[""]) + ["layers"],
        )


def weights(config, seed, held=None):
    return Weights(config, seed, held)


def program_params(config, weights_, held=None):
    """`models.lm`'s bound pytree holding the numbers of `weights_` (any
    reference-layout weights, of the experts `held`): arrays stacked by
    kind, W1 | W3 side by side. A stack is filled one reference array at
    a time and waited for, the largest stacks first and the ends last, so
    that never more than one such array is alive beside what is filled."""
    n = int(config["num_hidden_layers"])
    dense = int(config["first_k_dense_replace"])
    layers = weights_["layers"]
    held = held or getattr(layers, "held", None)
    dtype = jnp.dtype(config.get("dtype", "bfloat16"))

    def one(i, group, name):
        if isinstance(layers, Layers):
            return layers.array(i, name)
        return layers[i][group][name] if group else layers[i][name]

    def stack(members, group, names, shapes_):
        """The arrays `names` of the layers `members`, side by side on
        their last axis, a layer a row."""
        last = [shapes_[m][0][-1] for m in names]
        shape = (len(members),) + shapes_[names[0]][0][:-1] + (sum(last),)
        out = jnp.zeros(shape, dtype)
        for row, i in enumerate(members):
            for m, at in zip(names, np.cumsum([0] + last)):
                p = one(i, group, m)[None]
                start = (row,) + (0,) * (p.ndim - 2) + (int(at),)
                out = _write(out, p, tuple(np.int32(v) for v in start))
                out.block_until_ready()  # the host does not run ahead of the chip
        return out

    every = list(range(n))
    s_moe, s_dense = ffn_shapes(config, True, held), ffn_shapes(config, False)
    s_op, norms = op_shapes(config), shapes(config, 0)[""]
    params = {"moe": {}, "dense": {}, "mla": {}}
    params["moe"]["w_up"] = stack(every[dense:], "ffn", ["w1", "w3"], s_moe)
    params["moe"]["w_down"] = stack(every[dense:], "ffn", ["w2"], s_moe)
    if "shared_w1" in s_moe:
        params["moe"]["shared_up"] = stack(
            every[dense:], "ffn", ["shared_w1", "shared_w3"], s_moe)
        params["moe"]["shared_down"] = stack(every[dense:], "ffn", ["shared_w2"], s_moe)
    for name in ("router", "bias"):
        params["moe"][name] = stack(every[dense:], "ffn", [name], s_moe)
    params["dense"]["w_up"] = stack(every[:dense], "ffn", ["w1", "w3"], s_dense)
    params["dense"]["w_down"] = stack(every[:dense], "ffn", ["w2"], s_dense)
    for name, of in (("w_qa", "w_qa"), ("q_norm", "q_a_norm"), ("w_qb", "w_qb"),
                     ("w_kva", "w_kva"), ("kv_norm", "kv_a_norm"),
                     ("w_kvb", "w_kvb"), ("w_o", "wo")):
        params["mla"][name] = stack(every, "op", [of], s_op)
    for name in ("op_norm", "ffn_norm"):
        params[name] = stack(every, "", [name], norms)
    for name in ("embed", "head", "final_norm"):
        params[name] = weights_[name]
    return params
