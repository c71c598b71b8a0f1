"""Real-size compiles for a described (not attached) TPU v5e.

The TPU's compiler is installed next to the CPU backend and compiles
for a topology that is only described, so these tests raise here what
the chip's compiler would raise there — a refused kernel tiling, too
much VMEM, a program that does not fit 16 GB of HBM — at no chip time.
Nothing runs: a passing compile is not a chip run (`chip_smoke.py` is).

The topology is described inside the module-scoped `topo` fixture and
nowhere else: only one process may load the TPU library, the suite
runs under several xdist workers, and each worker imports every test
file — so nothing at import time (no top-level call, `skipif`
condition, `parametrize` argument or conftest hook) may touch it, and
these tests stay in this one file so one worker owns the library.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import tensorframes_tpu as tfs
from tensorframes_tpu import config, dsl
from tensorframes_tpu.models import MLP, TransformerLM
from tensorframes_tpu.ops.lowering import build_callable
from tensorframes_tpu.ops.pallas_kernels import flash_attention
from tensorframes_tpu.runtime.executor import Executor
from tensorframes_tpu.shape_policy import bucket_for


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here: nothing to ask
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes_dtypes):
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in shapes_dtypes
    ]
    lowered = jax.jit(fn).lower(*args)
    return lowered, lowered.compile()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_kernel(one_chip, dtype):
    lowered, compiled = _compile(
        functools.partial(flash_attention, causal=True),
        one_chip,
        *[((2048, 128), dtype)] * 3,
    )
    # compiled for the chip, not interpreted: the Mosaic kernel is there
    assert "tpu_custom_call" in lowered.as_text()
    assert compiled.memory_analysis() is not None


def test_mlp512_rows_program_at_1m_rows(one_chip):
    # BASELINE config 3 as map_rows dispatches it: the per-row graph
    # vmapped over the block, at the bucket rung 1,000,000 rows pad to
    graph, fetches = dsl.build(
        MLP([512, 512, 512, 10], seed=0).scoring_graph("features", block=False)
    )
    fn = jax.vmap(build_callable(graph, fetches, ["features"]))
    rows = bucket_for(1_000_000)
    assert rows == 1_048_576
    _, compiled = _compile(fn, one_chip, ((rows, 512), jnp.float32))
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9


def test_x_plus_3_at_the_200m_row_rung(one_chip):
    # the headline map cell: 200,000,000 rows pad to 268,435,456
    rows = bucket_for(200_000_000)
    assert rows == 268_435_456
    df = tfs.TensorFrame.from_dict({"x": np.zeros(4, np.float32)})
    graph, fetches = dsl.build((tfs.block(df, "x") + 3.0).named("z"))
    _compile(
        build_callable(graph, fetches, ["x"]), one_chip,
        ((rows,), jnp.float32),
    )


@pytest.mark.parametrize("column_rows", [400_000_000, 400_000_007])
def test_a_run_of_equal_blocks_is_one_fusion_over_its_rows(
    topo, monkeypatch, column_rows
):
    # `map_chain_200blocks`: x + 3 over 200 blocks of 2,000,000 rows as
    # the block group compiles it (`shape_policy._compile_group`): one
    # pass over the run's rows, whole column or a part of a longer one.
    # What the cell's traced `breakdown` would show, at no chip time.
    import re

    from tensorframes_tpu import shape_policy as sp
    from tensorframes_tpu.runtime.executor import ProgramLedger

    rows = 200 * 2_000_000
    df = tfs.TensorFrame.from_dict({"x": np.zeros(4, np.float32)})
    graph, fetches = dsl.build((tfs.block(df, "x") + 3.0).named("z"))
    book = ProgramLedger(
        ("map_blocks", "x_plus_3"),
        jax.jit(build_callable(graph, fetches, ["x"])),
    )
    made, compile_exact = [], sp._compile_exact

    def spy(*args):
        made.append(compile_exact(*args))
        return made[-1]

    monkeypatch.setattr(sp, "_compile_exact", spy)
    call = sp._compile_group(
        book, rows, [((column_rows,), np.dtype(np.float32))],
        topo.devices[0],
    )
    assert call is not None
    (compiled,) = made
    text = compiled.as_text()
    assert re.search(r"HloModule (\w+)", text).group(1) == "jit_fn"
    assert " while(" not in text and "dynamic-update-slice" not in text
    (fusion,) = re.findall(r"= (\S+) fusion\(", text)
    assert fusion.startswith("f32[400000000]")
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes == 0
    assert mem.output_size_in_bytes == rows * 4


class _Captured(Exception):
    pass


class _CaptureSegmentProgram(Executor):
    """Hands the test the keyed-aggregate segment program instead of
    running it: the jitted callable is built exactly as `aggregate`
    builds it, from a small frame with the real key count, and is then
    compiled at the real row count for the described chip."""

    def cached(self, kind, graph, fetches, feed_names, make):
        if kind.startswith("segagg-"):
            self.kind, self.program = kind, make()
            raise _Captured
        return super().cached(kind, graph, fetches, feed_names, make)


@pytest.mark.parametrize(
    "keys,onehot", [(16, True), (10_000, False)], ids=["onehot", "segment"]
)
def test_keyed_aggregate_program_at_10m_rows(one_chip, keys, onehot):
    # small frame, real key count: the key count shapes the program
    n = max(64, keys)
    df = tfs.TensorFrame.from_dict(
        {
            "k": (np.arange(n) % keys).astype(np.int32),
            "v": np.zeros((n, 8), np.float32),
        }
    )
    mean = dsl.reduce_mean(
        tfs.block(df, "v", tf_name="v_input"), axes=[0]
    ).named("v")
    ex = _CaptureSegmentProgram()
    # the one-hot MXU branch is what a TPU backend picks for <= 256
    # keys; no CPU run enters it by itself, so the test forces it
    with config.override(aggregate_onehot_keys=256):
        with pytest.raises(_Captured):
            tfs.aggregate(mean, tfs.group_by(df, "k"), executor=ex)
    assert ex.kind.endswith("-1" if onehot else "-0"), ex.kind
    rows = 10_000_000
    _compile(
        ex.program, one_chip,
        ((rows,), jnp.int32),       # group ids
        ((keys,), jnp.int32),       # per-group counts (mean)
        ((rows, 8), jnp.float32),   # the value column
    )


def test_transformer_train_step_through_the_kernel(one_chip, monkeypatch):
    # TransformerLM picks the kernel when the backend is a TPU; force
    # that branch here so the step compiles as it will on the chip:
    # forward through the per-head vmapped kernel, backward through its
    # custom_vjp
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lm = TransformerLM()
    shapes = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=one_chip),
        lm.params,
    )
    tokens = jax.ShapeDtypeStruct((256,), jnp.int32, sharding=one_chip)
    lowered = jax.jit(lm.train_step).lower(shapes, tokens)
    assert "tpu_custom_call" in lowered.as_text()
    lowered.compile()


# --- the language model of the benchmark's `lfm2-8b-a1b`, at its widths ---


def _lfm2_config():
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf", "configs", "lfm2-8b-a1b.json")) as f:
        return json.load(f)


def test_grouped_query_flash_attention_at_the_cells_shape(one_chip):
    # 8 rows x 32 query heads over 8 key/value heads of 64, 4,096
    # positions, bfloat16, 512-blocks: as `models.lm` calls the kernel
    lowered, compiled = _compile(
        functools.partial(flash_attention, causal=True, block_q=512, block_k=512),
        one_chip,
        ((8, 32, 4096, 64), jnp.bfloat16),
        ((8, 8, 4096, 64), jnp.bfloat16),
        ((8, 8, 4096, 64), jnp.bfloat16),
    )
    assert "tpu_custom_call" in lowered.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


def test_the_whole_scoring_program_at_the_cells_block(one_chip):
    """`lm.scoring_fn` of the configuration over one block of the cell (8
    windows of 4,096 ids) with the weights as arguments: it compiles for
    the chip with the kernel in it, its temporaries fit beside 6.5 GB of
    weights, and its three outputs are small."""
    from tensorframes_tpu.models import lm

    cfg = _lfm2_config()
    shapes = jax.eval_shape(lambda: lm.init_params(cfg, 0))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), shapes)
    tokens = jax.ShapeDtypeStruct((8, 4096), jnp.int32, sharding=one_chip)
    lowered = jax.jit(lm.scoring_fn(cfg)).lower(tokens, params)
    assert "tpu_custom_call" in lowered.as_text()
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    weights = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(shapes))
    assert 6.4e9 < weights < 6.7e9
    assert weights + memory.temp_size_in_bytes < 15.75 * 2**30
    out = compiled.out_info if hasattr(compiled, "out_info") else None
    assert memory.output_size_in_bytes < 8 * 2**20
    if out is not None:
        assert {k: v.shape for k, v in out.items()} == {
            "token_logprob": (8, 4096), "expert_load": (8, 8, 32),
            "expert_choice": (8, 8, 4096, 4)}


def test_expert_layer_is_a_grouped_matmul_under_the_names_the_benchmark_reads(
    one_chip,
):
    """One expert layer at the published widths (32,768 tokens top-4 over
    32 experts of 1,792): the two grouped matmuls are the chip's own
    ragged-dot kernel, under operation names that the configuration's
    `kernel_ops.moe_experts` matches (what `moe_expert_roofline` sums in a
    device trace) and that nothing else in the layer matches."""
    import re
    import sys

    from tensorframes_tpu.models import moe

    cfg = _lfm2_config()
    d, f, e = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["num_experts"]

    def layer(u, router, bias, w_up, w_down):
        idx, w = moe.route(u, router, bias, top_k=cfg["num_experts_per_tok"])
        return moe.held_experts(u, idx, w, w_up, w_down, (0, e))

    bf16 = jnp.bfloat16
    _, compiled = _compile(
        layer, one_chip, ((32768, d), bf16), ((d, e), bf16), ((e,), bf16),
        ((e, d, 2 * f), bf16), ((e, f, d), bf16),
    )
    root = __import__("os").path.dirname(__import__("os").path.dirname(
        __import__("os").path.abspath(__file__)))
    sys.path.insert(0, root)
    from perf.lib.trace import op_label

    pattern = re.compile(cfg["kernel_ops"]["moe_experts"])
    labels = [
        op_label(line.strip().removeprefix("ROOT "))
        for line in compiled.as_text().splitlines() if " = " in line
    ]
    matched = sorted({l for l in labels if pattern.search(l)})
    assert len(matched) == 2, matched
    assert all(l.startswith("ragged-dot-none") for l in matched)
    assert {l.split()[1] for l in matched} == {
        f"f32[{32768 * 4},{2 * f}]", f"f32[{32768 * 4},{d}]"}
    # no dense evaluation of every expert beside it: no convolution (a
    # plain dot on the chip) as large as an expert's matmul over all rows
    assert not [l for l in labels if l.startswith("convolution")
                and f"[{32768 * 4}," in l]


def _joyai_config():
    """`joyai-llm-flash` as the benchmark's runner hands it to the program:
    the file's published keys, without the ones `derived` lists."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf", "configs", "joyai-llm-flash.json")) as f:
        config = json.load(f)
    return config, {k: v for k, v in config.items() if k not in config["derived"]}


def test_latent_attention_kernel_at_the_cells_shape(one_chip):
    # one window of 32,768 positions: 32 heads with a score of a per-head
    # part (128) and a rotary part (64) whose key all heads share, values
    # of 128, bfloat16, 1,024-blocks: as `models.lm` calls the kernel
    shapes = [((1, 32, 32768, 128), jnp.bfloat16)] * 3 + [
        ((1, 32, 32768, 64), jnp.bfloat16), ((1, 1, 32768, 64), jnp.bfloat16)]
    lowered, compiled = _compile(
        lambda q, k, v, q2, k2: flash_attention(
            q, k, v, q2=q2, k2=k2, causal=True, scale=float(1 / np.sqrt(192)),
            block_q=1024, block_k=1024),
        one_chip, *shapes,
    )
    assert "tpu_custom_call" in lowered.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**29


def test_the_latent_scoring_program_at_the_cells_block(one_chip):
    """`lm.scoring_fn` of `joyai-llm-flash` at the published widths over one
    block of the cell (one window of 32,768 ids), the weights arguments: it
    compiles for the chip; its temporaries fit beside 10.35 GiB of weights;
    the operations that the configuration's `kernel_ops.mla_attention` and
    `kernel_ops.moe_experts` match are in it under those names (what
    `mla_attention_roofline` and `moe_expert_roofline` sum in a device
    trace), the grouped matmuls over whole parts of the window; no layer's
    expert weights are copied out of their stack; and the program holds no
    64-bit array (the chip's grouped matmul compiles beside none)."""
    import re
    import sys

    from tensorframes_tpu.models import lm

    config, cfg = _joyai_config()
    shapes = jax.eval_shape(lambda: lm.init_params(cfg, 0))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), shapes)
    tokens = jax.ShapeDtypeStruct((1, 32768), jnp.int32, sharding=one_chip)
    lowered = jax.jit(lm.scoring_fn(cfg)).lower(tokens, params)
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    weights = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(shapes))
    assert 11.0e9 < weights < 11.2e9  # 5,558 M parameters in bfloat16
    assert weights + memory.temp_size_in_bytes < 15.0 * 2**30
    assert memory.output_size_in_bytes < 8 * 2**20

    root = __import__("os").path.dirname(__import__("os").path.dirname(
        __import__("os").path.abspath(__file__)))
    sys.path.insert(0, root)
    from perf.lib.trace import op_label

    text = compiled.as_text()
    labels = [
        op_label(line.strip().removeprefix("ROOT "))
        for line in text.splitlines() if " = " in line
    ]
    attention = sorted({l for l in labels
                        if re.search(config["kernel_ops"]["mla_attention"], l)})
    assert attention == [l for l in attention if l.endswith("bf16[1,32,32768,128]")]
    assert len(attention) == 1, attention
    experts = sorted({l for l in labels
                      if re.search(config["kernel_ops"]["moe_experts"], l)})
    assert len(experts) == 2 and all(l.startswith("ragged-dot-none") for l in experts)
    widths = {l.split()[1].split(",")[1].rstrip("]") for l in experts}
    assert widths == {str(2 * 768), "2048"}, experts
    part = {int(l.split("[")[1].split(",")[0]) for l in experts}
    assert len(part) == 1 and (32768 * 8) % part.pop() == 0
    # a layer's 1.2 GB of experts stay where they are bound
    assert not re.search(r"= bf16\[(1,)?256,2048,1536\]\S* (fusion|copy|dynamic-slice)", text)
    assert not re.findall(r"\b[sufc](?:64|128)\[", text)


def _nemotron_config():
    """`nemotron-3-super-120b-a12b` as the benchmark's runner hands it to
    the program: the router's published width and the share held here."""
    import json
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from perf.runners.map_blocks_lm_hybrid import model_config

    with open(os.path.join(root, "perf", "configs", "nemotron-3-super-120b-a12b.json")) as f:
        config = json.load(f)
    return (config,) + model_config(config, False)


def test_state_space_scan_kernel_at_the_cells_shape(one_chip):
    # one window of 32,768 positions: 128 heads of 64 in 8 groups, state
    # 128, chunks of 128, bfloat16: as `models.lm` calls the kernel
    from tensorframes_tpu.ops.pallas_kernels import ssd_scan

    bf16, f32 = jnp.bfloat16, jnp.float32
    lowered, compiled = _compile(
        lambda *a: ssd_scan(*a, chunk=128), one_chip,
        ((1, 32768, 128, 64), bf16), ((1, 32768, 128), f32), ((128,), f32),
        ((1, 32768, 8, 128), bf16), ((1, 32768, 8, 128), bf16), ((128,), f32),
    )
    assert "tpu_custom_call" in lowered.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


def test_the_hybrid_scoring_program_at_the_cells_block(one_chip):
    """`lm.scoring_fn` of `nemotron-3-super-120b-a12b` at the published
    widths over one block of the cell (one window of 32,768 ids), experts
    0-127 of 512 held, the weights arguments: it compiles for the chip; its
    temporaries fit beside 8.66 GiB of weights; the operations that the
    configuration's `kernel_ops.ssd_scan` and `kernel_ops.moe_experts`
    match are in it under those names, the grouped matmuls over a step of
    the held rows' loop and not over every routed row's place; no layer's
    expert weights are copied out of their stack; and the program holds no
    64-bit array."""
    import re

    from tensorframes_tpu.models import lm, moe

    config, cfg, held = _nemotron_config()
    assert held == (0, 128) and cfg["n_routed_experts"] == 512
    shapes = jax.eval_shape(lambda: lm.init_params(cfg, 0, held))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), shapes)
    tokens = jax.ShapeDtypeStruct((1, 32768), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lm.scoring_fn(cfg, held=held)).lower(tokens, params).compile()
    memory = compiled.memory_analysis()
    weights = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(shapes))
    assert 9.25e9 < weights < 9.35e9  # 4,648 M parameters in bfloat16: 8.66 GiB
    assert weights + memory.temp_size_in_bytes < 15.0 * 2**30
    assert memory.output_size_in_bytes < 32 * 2**20

    from perf.lib.trace import op_label

    text = compiled.as_text()
    labels = [
        op_label(line.strip().removeprefix("ROOT "))
        for line in text.splitlines() if " = " in line
    ]
    scan = sorted({l for l in labels if re.search(config["kernel_ops"]["ssd_scan"], l)})
    assert len(scan) == 1 and scan[0].endswith("bf16[1,32768,8192]"), scan
    experts = sorted({l for l in labels
                      if re.search(config["kernel_ops"]["moe_experts"], l)})
    assert len(experts) == 2 and all(l.startswith("ragged-dot-none") for l in experts)
    assert {l.split()[1] for l in experts} == {
        f"f32[{moe.STEP_ROWS},2688]", f"f32[{moe.STEP_ROWS},1024]"}, experts
    attention = [l for l in set(labels) if l.startswith("lm.attention")]
    assert len(attention) == 1 and attention[0].endswith("bf16[1,32,32768,128]")
    # a layer's 1.4 GB of held experts stay where they are bound
    assert not re.search(r"= bf16\[(1,)?128,1024,2688\]\S* (fusion|copy|dynamic-slice)", text)
    assert not re.findall(r"\b[sufc](?:64|128)\[", text)


def _hy4_config():
    """`hy4-preview` as the benchmark's runner hands it to the program: the
    router's published width and the share held here."""
    import json
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from perf.runners.map_blocks_lm_hybrid import model_config

    with open(os.path.join(root, "perf", "configs", "hy4-preview.json")) as f:
        config = json.load(f)
    return (config,) + model_config(config, False)


def test_index_kernel_at_the_cells_shape(one_chip):
    # a block of 1,024 queries of 32 index heads of 128 against the
    # window's 16,384 keys, bfloat16 operands, float32 weights and scores:
    # as `models.lm._select` calls the kernel
    from tensorframes_tpu.ops.pallas_kernels import index_scores

    bf16, f32 = jnp.bfloat16, jnp.float32
    lowered, compiled = _compile(
        lambda q, k, w, start: index_scores(q, k, w, start, scale=float(1 / np.sqrt(128))),
        one_chip, ((1, 1024, 32, 128), bf16), ((1, 16384, 128), bf16),
        ((1, 1024, 32), f32), ((), jnp.int32),
    )
    assert "tpu_custom_call" in lowered.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**28


def test_index_top_k_kernel_at_the_cells_shape(one_chip):
    # a block of 1,024 queries' float32 scores over the window's 16,384
    # keys, the top-2,048: as `models.lm._select` calls the kernel
    from tensorframes_tpu.ops.pallas_kernels import index_top_k

    lowered, compiled = _compile(
        lambda scores, start: index_top_k(scores, start, k=2048),
        one_chip, ((1, 1024, 16384), jnp.float32), ((), jnp.int32),
    )
    assert "tpu_custom_call" in lowered.as_text()
    assert compiled.memory_analysis().output_size_in_bytes < 2**24


def test_sparse_attention_kernel_at_the_cells_shape(one_chip):
    # one window of 16,384 positions: 64 heads with a score of a per-head
    # part (192) and a rotary part (64) whose key all heads share, values
    # of 256, the selection mask, a sink a head, 512-blocks (1,024-blocks
    # do not fit the kernel's VMEM at these widths)
    from tensorframes_tpu.ops.pallas_kernels import sparse_attention

    bf16 = jnp.bfloat16
    lowered, compiled = _compile(
        lambda q, k, v, sel, sink, q2, k2: sparse_attention(
            q, k, v, sel, sink, q2=q2, k2=k2, scale=1 / 16.0, block=512),
        one_chip, ((1, 64, 16384, 192), bf16), ((1, 64, 16384, 192), bf16),
        ((1, 64, 16384, 256), bf16), ((1, 16384, 16384), jnp.int8), ((64,), jnp.float32),
        ((1, 64, 16384, 64), bf16), ((1, 1, 16384, 64), bf16),
    )
    assert "tpu_custom_call" in lowered.as_text()
    assert compiled.memory_analysis() is not None


def test_the_sparse_scoring_program_at_the_cells_block(one_chip):
    """`lm.scoring_fn` of `hy4-preview` at the published widths over one
    block of the cell (one window of 16,384 ids), experts 0-7 of 256 held,
    the weights arguments: it compiles for the chip as module
    `jit_lm_score`; its temporaries fit beside 6.04 GiB of weights; the
    operations that the configuration's `kernel_ops.dsa_index` and
    `kernel_ops.dsa_attention` match are in it under those names and
    shapes (what the four `dsa_*` readers sum in a device trace); the
    grouped matmuls run a step of the held rows' loop; and the program
    holds no 64-bit array."""
    import re

    from perf.lib.trace import op_label
    from tensorframes_tpu.models import lm, moe

    config, cfg, held = _hy4_config()
    assert held == (0, 8) and cfg["n_routed_experts"] == 256
    shapes = jax.eval_shape(lambda: lm.init_params(cfg, 0, held))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), shapes)
    tokens = jax.ShapeDtypeStruct((1, 16384), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lm.scoring_fn(cfg, held=held)).lower(tokens, params).compile()
    memory = compiled.memory_analysis()
    weights = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(shapes))
    assert 6.45e9 < weights < 6.55e9  # 3,244 M parameters in bfloat16: 6.04 GiB
    assert weights + memory.temp_size_in_bytes < 14.0 * 2**30
    assert memory.output_size_in_bytes < 160 * 2**20

    text = compiled.as_text()
    assert re.search(r"^HloModule jit_lm_score\b", text, re.M)
    labels = [
        op_label(line.strip().removeprefix("ROOT "))
        for line in text.splitlines() if " = " in line
    ]
    index = sorted({l for l in labels if re.search(config["kernel_ops"]["dsa_index"], l)})
    assert len(index) == 1 and index[0].endswith("f32[1,1024,16384]"), index
    assert re.match(r"^lm\.dsa_index\.\d+ ", index[0])
    attend = sorted({l for l in labels if re.search(config["kernel_ops"]["dsa_attention"], l)})
    assert len(attend) == 1 and attend[0].endswith("bf16[1,64,16384,256]"), attend
    assert re.match(r"^lm\.dsa\.\d+ ", attend[0])
    experts = sorted({l for l in labels if l.startswith("ragged-dot-none")})
    assert {l.split()[1] for l in experts} == {
        f"f32[{moe.STEP_ROWS},4096]", f"f32[{moe.STEP_ROWS},6144]"}, experts
    assert not re.findall(r"\b[sufc](?:64|128)\[", text)
    # the top-2,048 of a block's scores is the threshold kernel, named after
    # its scope, and no sort of them; the temporaries are no more than the
    # 7,241,536,000 B that the program holding that sort compiled to here
    assert not [l for l in text.splitlines() if " sort(" in l and "f32[1,1024,16384]" in l]
    top_k = [l for l in labels if re.match(r"^dsa\.top_k\.\d+ \(f32\[1,1024,1\]", l)]
    assert len(top_k) == 1, sorted(l for l in labels if "top_k" in l)
    assert re.search(r"= \(f32\[1,1024,1\]\S*, s32\[1,1024,1\]\S*, s32\[1,1024,2048\]\S*\) "
                     r"custom-call\(.*tpu_custom_call", text)
    assert memory.temp_size_in_bytes <= 7_241_536_000


def _trinity_config():
    """`trinity-mini` as the benchmark's runner hands it to the program."""
    import json
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from perf.runners.map_blocks_lm import model_config

    with open(os.path.join(root, "perf", "configs", "trinity-mini.json")) as f:
        config = json.load(f)
    return config, model_config(config, False)


def test_sliding_window_kernel_at_the_cells_shape(one_chip):
    # one window of 32,768 positions: 32 query heads over 4 key/value heads
    # of 128, a band of 2,048 keys, bfloat16, 1,024-blocks: as `models.lm`
    # calls the kernel for a sliding layer (3 key blocks a query block)
    bf16 = jnp.bfloat16
    lowered, compiled = _compile(
        functools.partial(flash_attention, causal=True, block_q=1024, block_k=1024,
                          window=2048),
        one_chip, ((1, 32, 32768, 128), bf16), ((1, 4, 32768, 128), bf16),
        ((1, 4, 32768, 128), bf16),
    )
    assert "tpu_custom_call" in lowered.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**29


def test_the_window_scoring_program_at_the_cells_block(one_chip):
    """`lm.scoring_fn` of `trinity-mini` at the published widths over one
    block of the cell (one window of 32,768 ids), every expert held, the
    weights arguments: it compiles for the chip as module `jit_lm_score`;
    its temporaries fit beside 7.90 GiB of weights; the sliding layers'
    kernel is one operation that `kernel_ops.swa_attention` matches, the
    full layer's another that it does not; the grouped matmuls are what
    `kernel_ops.moe_experts` matches; and the program holds no 64-bit
    array."""
    import re

    from perf.lib.trace import op_label
    from tensorframes_tpu.models import lm

    config, cfg = _trinity_config()
    shapes = jax.eval_shape(lambda: lm.init_params(cfg, 0))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), shapes)
    tokens = jax.ShapeDtypeStruct((1, 32768), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lm.scoring_fn(cfg)).lower(tokens, params).compile()
    memory = compiled.memory_analysis()
    weights = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(shapes))
    assert weights == 4_241_534_720 * 2  # 4,241,490,432 in matrices, 44,288 in norms' gains
    assert weights + memory.temp_size_in_bytes < 12.0 * 2**30
    assert memory.output_size_in_bytes < 8 * 2**20

    text = compiled.as_text()
    assert re.search(r"^HloModule jit_lm_score\b", text, re.M)
    labels = [
        op_label(line.strip().removeprefix("ROOT "))
        for line in text.splitlines() if " = " in line
    ]
    band = sorted({l for l in labels if re.search(config["kernel_ops"]["swa_attention"], l)})
    assert len(band) == 1 and band[0].endswith("bf16[1,32,32768,128]"), band
    full = sorted({l for l in labels if l.startswith("lm.attention")})
    assert len(full) == 1 and full[0].endswith("bf16[1,32,32768,128]"), full
    experts = sorted({l for l in labels if re.search(config["kernel_ops"]["moe_experts"], l)})
    assert len(experts) == 2 and all(l.startswith("ragged-dot-none") for l in experts)
    assert not re.findall(r"\b[sufc](?:64|128)\[", text)
    # the head is one kernel named after its scope; its float32 logits
    # exist nowhere in HBM: no chunk of (2,048, 200,192) and no flat relayout
    head = sorted({l for l in labels if re.match(r"lm\.head(\.\d+)? ", l)})
    assert len(head) == 1 and head[0].endswith("f32[32768,1]"), head
    assert "f32[409993216]" not in text and "f32[2048,200192]" not in text


@pytest.mark.parametrize("d,vocab", [(2048, 200192), (2048, 129280), (4096, 32768)])
def test_head_kernel_at_the_cells_shapes(one_chip, d, vocab):
    """`head_logprob` over one block of 32,768 tokens at its own tiles
    (`head_tiles`: the VMEM limit it asks for is within the chip's): the
    logits never take HBM, so the program's temporaries stay under 1 MiB."""
    from tensorframes_tpu.ops.pallas_kernels import head_logprob

    lowered, compiled = _compile(
        head_logprob, one_chip, ((32768, d), jnp.bfloat16), ((d, vocab), jnp.bfloat16),
        ((32768,), jnp.int32))
    assert "tpu_custom_call" in lowered.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20
