"""Seeded weights of a language-model configuration, made by the benchmark
and handed to both sides of `correct`.

The unit is one array of the REFERENCE's layout (the equations' names,
nothing stacked or fused: `perf/configs/lfm2-8b-a1b.reference.py`), made
on the device from the seed, the layer's number and the array's name
alone. The reference is given `weights(config, seed)`, whose layers make
themselves when asked for (the ends too), so that one layer is alive at
a time.
`program_params` writes the very same arrays, one at a time, into the
stacked and fused pytree `tensorframes_tpu.models.lm` takes as its bound
argument. The program's own `lm.init_params` is not used: a fault in how
the program stacks, fuses or indexes its weights is a fault `correct`
sees.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import datagen

NAMES = (
    "embed", "head", "final_norm", "op_norm", "ffn_norm", "w_in", "taps",
    "w_out", "wq", "wk", "wv", "q_norm", "k_norm", "wo", "w1", "w3", "w2",
    "router", "bias",
)
ENDS = -1  # the "layer" of embed, head and final_norm


def _sizes(config):
    heads = int(config["num_attention_heads"])
    return dict(
        d=int(config["hidden_size"]), v=int(config["vocab_size"]), heads=heads,
        kv=int(config["num_key_value_heads"]),
        hd=int(config.get("head_dim") or config["hidden_size"] // heads),
        k=int(config["conv_L_cache"]), f=int(config["intermediate_size"]),
        fe=int(config["moe_intermediate_size"]), e=int(config["num_experts"]),
    )


def op_shapes(config, kind):
    """{name: (shape, scale)} of an operator; scale None is a norm's gain."""
    z = _sizes(config)
    d, hd = z["d"], z["hd"]
    std = float(config.get("initializer_range", 0.02))
    if kind == "conv":
        return {"w_in": ((d, 3 * d), std),
                "taps": ((z["k"], d), float(1.0 / np.sqrt(z["k"]))),
                "w_out": ((d, d), std)}
    return {"wq": ((d, z["heads"] * hd), std), "wk": ((d, z["kv"] * hd), std),
            "wv": ((d, z["kv"] * hd), std), "q_norm": ((hd,), None),
            "k_norm": ((hd,), None), "wo": ((z["heads"] * hd, d), std)}


def ffn_shapes(config, experts, held=None):
    z = _sizes(config)
    d = z["d"]
    std = float(config.get("initializer_range", 0.02))
    if not experts:
        f = z["f"]
        return {"w1": ((d, f), std), "w3": ((d, f), std), "w2": ((f, d), std)}
    fe, count = z["fe"], (held or (0, z["e"]))[1]
    return {"router": ((d, z["e"]), std),
            "bias": ((z["e"],), float(config.get("router_bias_range", 0.1))),
            "w1": ((count, d, fe), std), "w3": ((count, d, fe), std),
            "w2": ((count, fe, d), float(config.get("expert_out_range", std)))}


def shapes(config, i, held=None):
    """{group: {name: (shape, scale)}} of layer `i` (`ENDS`: the ends)."""
    z = _sizes(config)
    d = z["d"]
    if i == ENDS:
        std = float(config.get("initializer_range", 0.02))
        return {"": {"embed": ((z["v"], d), std), "head": ((d, z["v"]), std),
                     "final_norm": ((d,), None)}}
    return {"": {"op_norm": ((d,), None), "ffn_norm": ((d,), None)},
            "op": op_shapes(config, config["layer_types"][i]),
            "ffn": ffn_shapes(config, i >= int(config["num_dense_layers"]), held)}


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, shape, scale, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    x = 1.0 + 0.05 * x if scale is None else jnp.float32(scale) * x
    return x.astype(dtype)


def array(config, seed, i, name, held=None):
    """The array `name` of layer `i`: normal(0, scale), a norm's gain 1 +
    normal(0, 0.05), rounded to the configuration's dtype."""
    (shape, scale), = [
        g[name] for g in shapes(config, i, held).values() if name in g
    ]
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
    ``"layers"``, every array made when asked for and kept by whoever
    asked."""

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


@functools.partial(jax.jit, donate_argnums=0)
def _write(stack, part, start):
    return jax.lax.dynamic_update_slice(stack, part, start)


def program_params(config, weights_, held=None):
    """`models.lm`'s bound pytree holding the numbers of `weights_` (any
    reference-layout weights, of the experts `held`): arrays stacked by
    kind, W1 | W3 side by side in ``w_up``, q | k | v in ``w_qkv``. A
    stack is filled one reference array at a time and waited for, the
    largest stacks first and the ends last, so that never more than one
    such array is alive beside what is filled and the peak of the filling
    is the weights themselves."""
    types = list(config["layer_types"])
    dense = int(config["num_dense_layers"])
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
        last = [shapes_[n][0][-1] for n in names]
        shape = (len(members),) + shapes_[names[0]][0][:-1] + (sum(last),)
        out = jnp.zeros(shape, dtype)
        for row, i in enumerate(members):
            for n, at in zip(names, np.cumsum([0] + last)):
                p = one(i, group, n)[None]
                start = (row,) + (0,) * (p.ndim - 2) + (int(at),)
                out = _write(out, p, tuple(np.int32(v) for v in start))
                out.block_until_ready()  # the host does not run ahead of the chip
        return out

    every = list(range(len(types)))
    conv = [i for i in every if types[i] == "conv"]
    attn = [i for i in every if types[i] != "conv"]
    norms = shapes(config, 0)[""]
    s_moe, s_dense = ffn_shapes(config, True, held), ffn_shapes(config, False)
    s_conv, s_attn = op_shapes(config, "conv"), op_shapes(config, "full_attention")
    params = {"moe": {}, "dense": {}, "conv": {}, "attn": {}}
    params["moe"]["w_up"] = stack(every[dense:], "ffn", ["w1", "w3"], s_moe)
    params["moe"]["w_down"] = stack(every[dense:], "ffn", ["w2"], s_moe)
    for name in ("router", "bias"):
        params["moe"][name] = stack(every[dense:], "ffn", [name], s_moe)
    params["dense"]["w_up"] = stack(every[:dense], "ffn", ["w1", "w3"], s_dense)
    params["dense"]["w_down"] = stack(every[:dense], "ffn", ["w2"], s_dense)
    for name in ("w_in", "taps", "w_out"):
        params["conv"][name] = stack(conv, "op", [name], s_conv)
    params["attn"]["w_qkv"] = stack(attn, "op", ["wq", "wk", "wv"], s_attn)
    for name, of in (("w_o", "wo"), ("q_norm", "q_norm"), ("k_norm", "k_norm")):
        params["attn"][name] = stack(attn, "op", [of], s_attn)
    for name in ("op_norm", "ffn_norm"):
        params[name] = stack(every, "", [name], norms)
    for name in ("embed", "head", "final_norm"):
        params[name] = weights_[name]
    return params
