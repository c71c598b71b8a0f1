"""Seeded weights of a configuration of sliding-window and full attention
mixed (`trinity-mini`), made by the benchmark and handed to both sides of
`correct`: `perf/lib/lm_weights.py`'s scheme (an array of the REFERENCE's
layout, `perf/configs/trinity-mini.reference.py`, made on the device from
the seed, the layer's number and the array's name alone; layers made when
asked for) for this family's arrays: the attention gate ``wg``, the two
post norms a layer, the expert bias, the shared expert.

`program_params` writes the same arrays, one at a time, into the stacked
pytree `tensorframes_tpu.models.lm` takes: the sliding layers' attention
in ``swa``, the full layers' in ``attn``, each with its ``w_g``."""

import jax.numpy as jnp
import numpy as np

from . import datagen
from .lm_weights import ENDS, _draw, _write

NAMES = (
    "embed", "head", "final_norm", "op_norm", "op_post_norm", "ffn_norm", "ffn_post_norm",
    "wq", "wk", "wv", "wg", "q_norm", "k_norm", "wo", "w1", "w3", "w2", "router", "bias",
    "shared_w1", "shared_w3", "shared_w2",
)
NORMS = ("op_norm", "op_post_norm", "ffn_norm", "ffn_post_norm")


def shapes(config, i, held=None):
    """{group: {name: (shape, scale)}} of layer `i` (`ENDS`: the ends);
    scale None is a norm's gain."""
    d, v = int(config["hidden_size"]), int(config["vocab_size"])
    heads, kv = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    hd = int(config.get("head_dim") or d // heads)
    std = float(config.get("initializer_range", 0.02))
    if i == ENDS:
        return {"": {"embed": ((v, d), std), "head": ((d, v), std), "final_norm": ((d,), None)}}
    op = {"wq": ((d, heads * hd), std), "wk": ((d, kv * hd), std), "wv": ((d, kv * hd), std),
          "wg": ((d, heads * hd), std), "q_norm": ((hd,), None), "k_norm": ((hd,), None),
          "wo": ((heads * hd, d), std)}
    if i < int(config["num_dense_layers"]):
        f = int(config["intermediate_size"])
        ffn = {"w1": ((d, f), std), "w3": ((d, f), std), "w2": ((f, d), std)}
    else:
        fe, e = int(config["moe_intermediate_size"]), int(config["num_experts"])
        count = (held or (0, e))[1]
        ffn = {"router": ((d, e), std),
               "bias": ((e,), float(config.get("router_bias_range", 0.1))),
               "w1": ((count, d, fe), std), "w3": ((count, d, fe), std),
               "w2": ((count, fe, d), std), "shared_w1": ((d, fe), std),
               "shared_w3": ((d, fe), std), "shared_w2": ((fe, d), std)}
    return {"": {n: ((d,), None) for n in NORMS}, "op": op, "ffn": ffn}


def array(config, seed, i, name, held=None):
    """The array `name` of layer `i`: normal(0, scale), a norm's gain 1 +
    normal(0, 0.05), rounded to the configuration's dtype."""
    import jax

    (shape, scale), = [g[name] for g in shapes(config, i, held).values() if name in g]
    key = jax.random.PRNGKey(int(datagen.seed_word(seed)) & 0x7FFFFFFF)
    key = jax.random.fold_in(jax.random.fold_in(key, i + 1), NAMES.index(name))
    return _draw(key, shape, scale, jnp.dtype(config.get("dtype", "bfloat16")))


class Layers:
    """The layers in the reference's layout, each made when asked for."""

    def __init__(self, config, seed, held=None):
        self.config, self.seed, self.held = config, seed, held

    def __len__(self):
        return len(self.config["layer_types"])

    def array(self, i, name):
        return array(self.config, self.seed, i, name, self.held)

    def __getitem__(self, i):
        groups = shapes(self.config, i, self.held)
        out = {n: self.array(i, n) for n in groups[""]}
        out.update({g: {n: self.array(i, n) for n in groups[g]} for g in ("op", "ffn")})
        return out


class Weights:
    """What the reference's `forward` takes, as a mapping: the ends and
    ``"layers"``, every array made when asked for."""

    def __init__(self, config, seed, held=None):
        self.config, self.seed = config, seed
        self.layers = Layers(config, seed, held)

    def keys(self):
        return list(shapes(self.config, ENDS)[""]) + ["layers"]

    def __getitem__(self, name):
        if name == "layers":
            return self.layers
        return array(self.config, self.seed, ENDS, name)


def weights(config, seed, held=None):
    return Weights(config, seed, held)


def program_params(config, weights_):
    """`models.lm`'s bound pytree holding the numbers of `weights_`: arrays
    stacked by kind, W1 | W3 side by side in ``w_up`` (the shared expert's
    in ``shared_up``), q | k | v in ``w_qkv``. A stack is filled one
    reference array at a time and waited for, the largest stacks first and
    the ends last, so that the peak of the filling is the weights
    themselves."""
    types = list(config["layer_types"])
    dense = int(config["num_dense_layers"])
    layers = weights_["layers"]
    dtype = jnp.dtype(config.get("dtype", "bfloat16"))

    def one(i, group, name):
        if isinstance(layers, Layers):
            return layers.array(i, name)
        return layers[i][group][name] if group else layers[i][name]

    def stack(members, group, names):
        """The arrays `names` of the layers `members`, side by side on
        their last axis, a layer a row."""
        of = shapes(config, members[0], getattr(layers, "held", None))[group]
        last = [of[n][0][-1] for n in names]
        out = jnp.zeros((len(members),) + of[names[0]][0][:-1] + (sum(last),), dtype)
        for row, i in enumerate(members):
            for n, at in zip(names, np.cumsum([0] + last)):
                p = one(i, group, n)[None]
                start = (row,) + (0,) * (p.ndim - 2) + (int(at),)
                out = _write(out, p, tuple(np.int32(v) for v in start))
                out.block_until_ready()  # the host does not run ahead of the chip
        return out

    every = list(range(len(types)))
    moe = every[dense:]
    params = {"moe": {
        "w_up": stack(moe, "ffn", ["w1", "w3"]), "w_down": stack(moe, "ffn", ["w2"]),
        "router": stack(moe, "ffn", ["router"]), "bias": stack(moe, "ffn", ["bias"]),
        "shared_up": stack(moe, "ffn", ["shared_w1", "shared_w3"]),
        "shared_down": stack(moe, "ffn", ["shared_w2"]),
    }}
    if dense:
        params["dense"] = {"w_up": stack(every[:dense], "ffn", ["w1", "w3"]),
                           "w_down": stack(every[:dense], "ffn", ["w2"])}
    for kind, members in (("attn", [i for i in every if types[i] == "full_attention"]),
                          ("swa", [i for i in every if types[i] == "sliding_attention"])):
        if members:
            params[kind] = {"w_qkv": stack(members, "op", ["wq", "wk", "wv"])}
            for name, of in (("w_o", "wo"), ("w_g", "wg"), ("q_norm", "q_norm"),
                             ("k_norm", "k_norm")):
                params[kind][name] = stack(members, "op", [of])
    for name in NORMS:
        params[name] = stack(every, "", [name])
    for name in ("embed", "head", "final_norm"):
        params[name] = weights_[name]
    return params
