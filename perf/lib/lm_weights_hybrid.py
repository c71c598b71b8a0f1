"""Seeded weights of a hybrid state-space language-model configuration
(`nemotron-3-super-120b-a12b`: layers of one mixer, by
`hybrid_override_pattern`), made by the benchmark and handed to both sides
of `correct`: `lm_weights.py`'s scheme for this family's layout.

The unit is one array of the REFERENCE's layout (the equations' names,
nothing stacked or fused: `perf/configs/nemotron-3-super-120b-a12b.
reference.py`), made on the device from the seed, the layer's number and
the array's name alone. The reference is given `weights(config, seed,
held)`: a layer's ``mixer`` makes itself when asked for (the ends too), so
that one layer is alive at a time. `program_params` writes the very same
arrays, one at a time, into the stacked pytree `tensorframes_tpu.models.lm`
takes as its bound argument (``[z | x | B | C | dt]`` side by side in the
state-space mixer's ``w_in``, the three convolutions' taps in ``conv_w``,
``q | k | v`` in ``w_qkv``). The program's own `lm.init_params` is not
used: a fault in how the program stacks, fuses or indexes its weights is a
fault `correct` sees. The configuration is read under its published key
names; ``held = (first, count)`` makes the weights of those routed experts
alone (the chip's share; the router keeps its width).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import datagen
from .lm_weights import _write
from .lm_weights_latent import Part

NAMES = (
    "embed", "head", "final_norm", "norm",
    "w_z", "w_x", "w_B", "w_C", "w_dt", "conv_x", "conv_B", "conv_C",
    "conv_bx", "conv_bB", "conv_bC", "dt_bias", "A_log", "D", "gate_norm", "w_out",
    "wq", "wk", "wv", "wo",
    "router", "bias", "w_l1", "w_l2", "w1", "w2", "shared_w1", "shared_w2",
)
ENDS = -1  # the "layer" of embed, head and final_norm


def _std(config):
    return float(config.get("initializer_range", 0.02))


def mixer_shapes(config, kind, held=None):
    """{name: (shape, scale)} of a layer's mixer of `kind` ("M", "*", "E");
    scale None is a norm's gain, a string one of `_draw`'s own rules."""
    d, std = int(config["hidden_size"]), _std(config)
    if kind == "M":
        heads, width = int(config["mamba_num_heads"]), int(config["mamba_head_dim"])
        inner, gn = heads * width, int(config["n_groups"]) * int(config["ssm_state_size"])
        k = int(config["conv_kernel"])
        tap = float(1.0 / np.sqrt(k))
        return {
            "w_z": ((d, inner), std), "w_x": ((d, inner), std),
            "w_B": ((d, gn), std), "w_C": ((d, gn), std), "w_dt": ((d, heads), std),
            "conv_x": ((k, inner), tap), "conv_B": ((k, gn), tap), "conv_C": ((k, gn), tap),
            "conv_bx": ((inner,), std), "conv_bB": ((gn,), std), "conv_bC": ((gn,), std),
            "dt_bias": ((heads,), "dt_bias"), "A_log": ((heads,), "A_log"),
            "D": ((heads,), "one"), "gate_norm": ((inner,), None),
            "w_out": ((inner, d), std),
        }
    if kind == "*":
        heads, kv = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
        hd = int(config.get("head_dim") or d // heads)
        return {"wq": ((d, heads * hd), float(config.get("query_out_range", std))),
                "wk": ((d, kv * hd), std), "wv": ((d, kv * hd), std),
                "wo": ((heads * hd, d), std)}
    e, fe = int(config["n_routed_experts"]), int(config["moe_intermediate_size"])
    latent = int(config["moe_latent_size"])
    fs = int(config["moe_shared_expert_intermediate_size"])
    count = (held or (0, e))[1]
    return {
        "router": ((d, e), std),
        "bias": ((e,), float(config.get("router_bias_range", 0.1))),
        "w_l1": ((d, latent), std), "w_l2": ((latent, d), std),
        "w1": ((count, latent, fe), std),
        "w2": ((count, fe, latent), float(config.get("expert_out_range", std))),
        "shared_w1": ((d, fs), std), "shared_w2": ((fs, d), std),
    }


def shapes(config, i, held=None):
    """{group: {name: (shape, scale)}} of layer `i` (`ENDS`: the ends)."""
    d, v, std = int(config["hidden_size"]), int(config["vocab_size"]), _std(config)
    if i == ENDS:
        return {"": {"embed": ((v, d), std), "head": ((d, v), std),
                     "final_norm": ((d,), None)}}
    kind = config["hybrid_override_pattern"][i]
    return {"": {"norm": ((d,), None)}, "mixer": mixer_shapes(config, kind, held)}


def _draw(config, key, shape, scale, dtype):
    """normal(0, scale); a norm's gain 1 + normal(0, 0.05); "one" 1;
    "A_log" the log of uniform(1, 16); "dt_bias" the inverse softplus of
    log-uniform(time_step_min, time_step_max) floored at time_step_floor."""
    f32 = jnp.float32
    if scale == "one":
        x = jnp.ones(shape, f32)
    elif scale == "A_log":
        x = jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    elif scale == "dt_bias":
        lo, hi = (float(np.log(config.get(k, v))) for k, v in (
            ("time_step_min", 0.001), ("time_step_max", 0.1)))
        dt = jnp.exp(jax.random.uniform(key, shape, f32, lo, hi))
        dt = jnp.maximum(dt, f32(config.get("time_step_floor", 1e-4)))
        x = dt + jnp.log(-jnp.expm1(-dt))  # softplus(x) = dt
    elif len(shape) == 3:
        # an expert at a time: the float32 draw of 128 experts at once
        # would stand 3 GB of temporaries beside the stacks being filled
        return _by_expert(key, shape, float(scale), dtype)
    else:
        x = jax.random.normal(key, shape, f32)
        x = 1.0 + 0.05 * x if scale is None else f32(scale) * x
    return x.astype(dtype)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _by_expert(key, shape, scale, dtype):
    return jax.lax.map(
        lambda k: (jnp.float32(scale) * jax.random.normal(k, shape[1:], jnp.float32)
                   ).astype(dtype),
        jax.random.split(key, shape[0]))


def array(config, seed, i, name, held=None):
    """The array `name` of layer `i`, rounded to the configuration's dtype."""
    (shape, scale), = [
        g[name] for g in shapes(config, i, held).values() if name in g
    ]
    key = jax.random.PRNGKey(int(datagen.seed_word(seed)) & 0x7FFFFFFF)
    key = jax.random.fold_in(jax.random.fold_in(key, i + 1), NAMES.index(name))
    return _draw(config, key, shape, scale, jnp.dtype(config.get("dtype", "bfloat16")))


class Layers:
    """The layers in the reference's layout: a layer is a mapping whose
    norm and ``mixer`` make themselves when asked for."""

    def __init__(self, config, seed, held=None):
        self.config, self.seed, self.held = config, seed, held

    def __len__(self):
        return len(self.config["hybrid_override_pattern"])

    def array(self, i, name):
        return array(self.config, self.seed, i, name, self.held)

    def __getitem__(self, i):
        groups = shapes(self.config, i, self.held)

        def one(name):
            if name in groups[""]:
                return self.array(i, name)
            # what a jitted reference function takes: a plain dict, made now
            return {n: self.array(i, n) for n in groups[name]}

        return Part(one, list(groups[""]) + ["mixer"])


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


# the program's stacks: {kind of layer: (stack, {its array: the reference's
# arrays, side by side on their last axis})}
STACKS = {
    "E": ("moe", {"w_up": ["w1"], "w_down": ["w2"], "shared_up": ["shared_w1"],
                  "shared_down": ["shared_w2"], "latent_in": ["w_l1"],
                  "latent_out": ["w_l2"], "router": ["router"], "bias": ["bias"]}),
    "M": ("ssm", {"w_in": ["w_z", "w_x", "w_B", "w_C", "w_dt"],
                  "conv_w": ["conv_x", "conv_B", "conv_C"],
                  "conv_b": ["conv_bx", "conv_bB", "conv_bC"], "dt_bias": ["dt_bias"],
                  "A_log": ["A_log"], "D": ["D"], "norm": ["gate_norm"],
                  "w_out": ["w_out"]}),
    "*": ("attn", {"w_qkv": ["wq", "wk", "wv"], "w_o": ["wo"]}),
}


def program_params(config, weights_, held=None):
    """`models.lm`'s bound pytree holding the numbers of `weights_` (any
    reference-layout weights, of the experts `held`): arrays stacked by
    kind. A stack is filled one reference array at a time and waited for,
    the experts' first and the ends last, so that never more than one such
    array is alive beside what is filled."""
    pattern = config["hybrid_override_pattern"]
    layers = weights_["layers"]
    held = held or getattr(layers, "held", None)
    dtype = jnp.dtype(config.get("dtype", "bfloat16"))

    def one(i, group, name):
        if isinstance(layers, Layers):
            return layers.array(i, name)
        return layers[i][group][name] if group else layers[i][name]

    def stack(members, group, names, shapes_):
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

    params = {}
    for kind, (name, arrays) in STACKS.items():
        members = [i for i, ch in enumerate(pattern) if ch == kind]
        if members:
            shapes_ = mixer_shapes(config, kind, held)
            params[name] = {ours: stack(members, "mixer", theirs, shapes_)
                            for ours, theirs in arrays.items()}
    every = list(range(len(pattern)))
    params["op_norm"] = stack(every, "", ["norm"], shapes(config, 0)[""])
    for name in ("embed", "head", "final_norm"):
        params[name] = weights_[name]
    return params
