"""Seeded weights of a sparse-attention language-model configuration
(`hy4-preview`: gated latent attention on a learned sparse index,
hyper-connections, a held share of the routed experts), made by the
benchmark and handed to both sides of `correct`: `lm_weights.py`'s scheme
for this family's layout.

The unit is one array of the REFERENCE's layout (the equations' names,
nothing stacked or fused: `perf/configs/hy4-preview.reference.py`), made on
the device from the seed, the layer's number, the array's group and its
name alone. The reference is given `weights(config, seed, held)`: a
layer's groups make themselves when asked for (the ends too), so that one
part is alive at a time. `program_params` writes the very same arrays, one
at a time, into the stacked pytree `tensorframes_tpu.models.lm` takes as
its bound argument (W1 | W3 side by side, ``phi_pre | phi_post | phi_res``
and ``b_pre | b_post | b_res`` side by side and the two sublayers' maps
stacked, the indexers of the ``full`` layers alone). The program's own
`lm.init_params` is not used: a fault in how the program stacks, fuses or
indexes its weights is a fault `correct` sees. The configuration is read
under its published key names; ``held = (first, count)`` makes the
weights of those routed experts alone (the router keeps its width).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import datagen
from .lm_weights import _write
from .lm_weights_latent import Part

GROUPS = ("", "op", "ffn", "hc_op", "hc_ffn")
NAMES = (
    "embed", "head", "final_norm", "hc_head_phi", "hc_head_alpha", "hc_head_bias",
    "op_norm", "ffn_norm", "w_qa", "q_a_norm", "w_qb", "w_kva", "kv_a_norm", "w_kvb",
    "wo", "w_g", "sink", "w_qI", "w_kI", "kI_norm", "kI_bias", "w_w",
    "w1", "w3", "w2", "router", "shared_w1", "shared_w3", "shared_w2",
    "phi_pre", "phi_post", "phi_res", "alpha", "b_pre", "b_post", "b_res",
)
ENDS = -1  # the "layer" of the ends


def _std(config):
    return float(config.get("initializer_range", 0.02))


def op_shapes(config, full):
    """{name: (shape, scale)} of a layer's attention (and, on a ``full``
    layer, its indexer); scale None is a norm's gain, a string one of
    `_draw`'s rules."""
    d, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    rq, rkv = int(config["q_lora_rank"]), int(config["kv_lora_rank"])
    dn, dr = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    dv, std = int(config["v_head_dim"]), _std(config)
    out = {
        "w_qa": ((d, rq), std), "q_a_norm": ((rq,), None),
        "w_qb": ((rq, heads * (dn + dr)), float(config.get("query_out_range", std))),
        "w_kva": ((d, rkv + dr), std), "kv_a_norm": ((rkv,), None),
        "w_kvb": ((rkv, heads * (dn + dv)), std), "wo": ((heads * dv, d), std),
        "w_g": ((d, heads * dv), std), "sink": ((heads,), "sink"),
    }
    if full:
        ih, ihd = int(config["index_n_heads"]), int(config["index_head_dim"])
        out.update({"w_qI": ((rq, ih * ihd), std), "w_kI": ((d, ihd), std),
                    "kI_norm": ((ihd,), None), "kI_bias": ((ihd,), std),
                    "w_w": ((d, ih), std)})
    return out


def ffn_shapes(config, experts, held=None):
    d, std = int(config["hidden_size"]), _std(config)
    if not experts:
        f = int(config["intermediate_size"])
        return {"w1": ((d, f), std), "w3": ((d, f), std), "w2": ((f, d), std)}
    fe, e = int(config["moe_intermediate_size"]), int(config["n_routed_experts"])
    count = (held or (0, e))[1]
    fs = int(config["n_shared_experts"]) * fe
    return {"router": ((d, e), std),
            "w1": ((count, d, fe), std), "w3": ((count, d, fe), std),
            "w2": ((count, fe, d), float(config.get("expert_out_range", std))),
            "shared_w1": ((d, fs), std), "shared_w3": ((d, fs), std),
            "shared_w2": ((fs, d), std)}


def hc_shapes(config):
    """A sublayer's hyper-connection maps."""
    n, d = int(config["hc_mult"]), int(config["hidden_size"])
    std = _std(config)
    return {"phi_pre": ((n * d, n), std), "phi_post": ((n * d, n), std),
            "phi_res": ((n * d, n * n), std), "alpha": ((3,), "alpha"),
            "b_pre": ((n,), "bias"), "b_post": ((n,), "bias"), "b_res": ((n * n,), "b_res")}


def _dense_layers(config):
    return list(config["mlp_layer_types"]).count("dense")


def shapes(config, i, held=None):
    """{group: {name: (shape, scale)}} of layer `i` (`ENDS`: the ends)."""
    d, v, std = int(config["hidden_size"]), int(config["vocab_size"]), _std(config)
    n = int(config["hc_mult"])
    if i == ENDS:
        return {"": {"embed": ((v, d), std), "head": ((d, v), std),
                     "final_norm": ((d,), None), "hc_head_phi": ((n * d, n), std),
                     "hc_head_alpha": ((1,), "alpha"), "hc_head_bias": ((n,), "bias")}}
    return {"": {"op_norm": ((d,), None), "ffn_norm": ((d,), None)},
            "op": op_shapes(config, config["indexer_types"][i] == "full"),
            "ffn": ffn_shapes(config, i >= _dense_layers(config), held),
            "hc_op": hc_shapes(config), "hc_ffn": hc_shapes(config)}


def _draw(config, key, shape, scale, dtype):
    """normal(0, scale); a norm's gain 1 + normal(0, 0.05); "sink"
    normal(sink_mean, sink_range); "alpha" normal(0, hc_alpha_range);
    "bias" normal(0, hc_bias_range); "b_res" that plus hc_diagonal on the
    diagonal of the n x n mixing."""
    f32 = jnp.float32
    if len(shape) == 3:  # an expert at a time
        return _by_expert(key, shape, float(scale), dtype)
    x = jax.random.normal(key, shape, f32)
    if scale is None:
        x = 1.0 + 0.05 * x
    elif scale == "sink":
        x = f32(config.get("sink_mean", 0.0)) + f32(config.get("sink_range", 1.0)) * x
    elif scale == "alpha":
        x = f32(config.get("hc_alpha_range", 0.3)) * x
    elif scale in ("bias", "b_res"):
        x = f32(config.get("hc_bias_range", 1.0)) * x
        if scale == "b_res":
            n = int(round(np.sqrt(shape[0])))
            x = x + f32(config.get("hc_diagonal", 2.0)) * jnp.eye(n, dtype=f32).reshape(-1)
    else:
        x = f32(scale) * x
    return x.astype(dtype)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _by_expert(key, shape, scale, dtype):
    return jax.lax.map(
        lambda k: (jnp.float32(scale) * jax.random.normal(k, shape[1:], jnp.float32)
                   ).astype(dtype),
        jax.random.split(key, shape[0]))


def array(config, seed, i, group, name, held=None):
    """The array `name` of `group` of layer `i`, rounded to the
    configuration's dtype."""
    shape, scale = shapes(config, i, held)[group][name]
    key = jax.random.PRNGKey(int(datagen.seed_word(seed)) & 0x7FFFFFFF)
    key = jax.random.fold_in(jax.random.fold_in(key, i + 1), GROUPS.index(group))
    key = jax.random.fold_in(key, NAMES.index(name))
    return _draw(config, key, shape, scale, jnp.dtype(config.get("dtype", "bfloat16")))


class Layers:
    """The layers in the reference's layout: a layer is a mapping whose
    norms and groups make themselves when asked for."""

    def __init__(self, config, seed, held=None):
        self.config, self.seed, self.held = config, seed, held

    def __len__(self):
        return int(self.config["num_hidden_layers"])

    def array(self, i, group, name):
        return array(self.config, self.seed, i, group, name, self.held)

    def __getitem__(self, i):
        groups = shapes(self.config, i, self.held)

        def one(name):
            if name in groups[""]:
                return self.array(i, "", name)
            # what a jitted reference function takes: a plain dict, made now
            return {n: self.array(i, name, n) for n in groups[name]}

        return Part(one, list(groups[""]) + list(GROUPS[1:]))


class Weights(Part):
    """What the reference's `forward` takes: the ends and ``"layers"``,
    every array made when asked for and kept by whoever asked."""

    def __init__(self, config, seed, held=None):
        self.layers = Layers(config, seed, held)
        super().__init__(
            lambda name: self.layers if name == "layers"
            else array(config, seed, ENDS, "", name),
            list(shapes(config, ENDS)[""]) + ["layers"],
        )


def weights(config, seed, held=None):
    return Weights(config, seed, held)


def program_params(config, weights_, held=None):
    """`models.lm`'s bound pytree holding the numbers of `weights_` (any
    reference-layout weights, of the experts `held`): arrays stacked by
    kind. A stack is filled one reference array at a time and waited for,
    the experts' first and the ends last, so that never more than one such
    array is alive beside what is filled."""
    n_layers = int(config["num_hidden_layers"])
    dense = _dense_layers(config)
    layers = weights_["layers"]
    held = held or getattr(layers, "held", None)
    dtype = jnp.dtype(config.get("dtype", "bfloat16"))

    def one(i, group, name):
        if isinstance(layers, Layers):
            return layers.array(i, group, name)
        return layers[i][group][name] if group else layers[i][name]

    def stack(members, names, shapes_):
        """The arrays `names` of `members` ((layer, group) a member, or a
        list of them: one more axis), side by side on their last axis."""
        members = [m if isinstance(m, list) else [m] for m in members]
        inner = len(members[0])
        last = [shapes_[n][0][-1] for n in names]
        shape = (len(members), inner) + shapes_[names[0]][0][:-1] + (sum(last),)
        out = jnp.zeros(shape, dtype)
        for row, parts in enumerate(members):
            for j, (i, group) in enumerate(parts):
                for n, at in zip(names, np.cumsum([0] + last)):
                    p = one(i, group, n)[None, None]
                    start = (row, j) + (0,) * (p.ndim - 3) + (int(at),)
                    out = _write(out, p, tuple(np.int32(v) for v in start))
                    out.block_until_ready()  # the host does not run ahead of the chip
        return out if inner > 1 else out[:, 0]

    every = list(range(n_layers))
    full = [i for i in every if config["indexer_types"][i] == "full"]
    s_moe, s_dense = ffn_shapes(config, True, held), ffn_shapes(config, False)
    s_op, s_hc, norms = op_shapes(config, True), hc_shapes(config), shapes(config, 0)[""]
    ffn = lambda members: [(i, "ffn") for i in members]
    params = {"moe": {}, "dense": {}, "mla": {}, "index": {}, "hc": {}}
    for ours, theirs in (("w_up", ["w1", "w3"]), ("w_down", ["w2"]), ("router", ["router"]),
                         ("shared_up", ["shared_w1", "shared_w3"]),
                         ("shared_down", ["shared_w2"])):
        params["moe"][ours] = stack(ffn(every[dense:]), theirs, s_moe)
    params["dense"]["w_up"] = stack(ffn(every[:dense]), ["w1", "w3"], s_dense)
    params["dense"]["w_down"] = stack(ffn(every[:dense]), ["w2"], s_dense)
    for ours, theirs in (("w_qa", "w_qa"), ("q_norm", "q_a_norm"), ("w_qb", "w_qb"),
                         ("w_kva", "w_kva"), ("kv_norm", "kv_a_norm"), ("w_kvb", "w_kvb"),
                         ("w_o", "wo"), ("w_g", "w_g"), ("sink", "sink")):
        params["mla"][ours] = stack([(i, "op") for i in every], [theirs], s_op)
    for ours, theirs in (("w_q", "w_qI"), ("w_k", "w_kI"), ("k_norm", "kI_norm"),
                         ("k_bias", "kI_bias"), ("w_w", "w_w")):
        params["index"][ours] = stack([(i, "op") for i in full], [theirs], s_op)
    both = [[(i, "hc_op"), (i, "hc_ffn")] for i in every]
    params["hc"]["phi"] = stack(both, ["phi_pre", "phi_post", "phi_res"], s_hc)
    params["hc"]["alpha"] = stack(both, ["alpha"], s_hc)
    params["hc"]["bias"] = stack(both, ["b_pre", "b_post", "b_res"], s_hc)
    for name in ("op_norm", "ffn_norm"):
        params[name] = stack([(i, "") for i in every], [name], norms)
    params["hc_head"] = {"phi": weights_["hc_head_phi"], "alpha": weights_["hc_head_alpha"],
                         "bias": weights_["hc_head_bias"]}
    for name in ("embed", "head", "final_norm"):
        params[name] = weights_[name]
    return params
