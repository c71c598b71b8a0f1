"""chip_smoke.py — the verb path on one TPU chip, checked phase by phase.

    python chip_smoke.py                 # one TPU v5e chip (what the driver runs)
    python chip_smoke.py --chips 4       # the several-chip path only, on four
    python chip_smoke.py --rehearse      # tiny sizes, CPU accepted, kernels
                                         # interpreted; never prints the
                                         # success line

Drives the system's main path once through the entry points a user
calls (`import tensorframes_tpu as tfs`): the verbs at the sizes
`BASELINE.md` tracks (the x + 3 chain and the `map_rows` MLP are cells of
the benchmark, `perf/`), a frozen Inception-v3 GraphDef scored at 299 px, a
`tfs.serving` endpoint under concurrent clients, a relational plan over
parquet shards, and the Pallas attention kernel with three
`TransformerLM.train_step`s. Every result is compared with a numpy (or
TF-session) reference computed here from seeded data, and every output
array is asserted to live on a TPU device. One process owns the chip;
the only child is the TensorFlow freeze, which never starts a JAX backend.

Output: one JSON object per phase on its own line, then — only on a
TPU, outside --rehearse, when every phase passed — the contract's last
line `{"ok": true, "device": {...}}`. The first failing phase raises:
non-zero exit, no success line. No TPU: non-zero exit before any phase.
The phase times are set-up evidence, not speeds (no benchmark yet).
"""

import argparse
import contextlib
import functools
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# Real sizes: BASELINE.md's tracked configs.
REAL = dict(
    map_chain=5,
    red_blocks=16, red_block_rows=4_194_304, red_dim=4,
    stream_chunks=4, stream_chunk_rows=16_777_216,
    agg_rows=10_000_000, agg_dim=8, agg_keys=(16, 10_000),
    mlp_sizes=(512, 512, 512, 10),
    inception_hw=299, inception_images=64,
    serve_clients=8, serve_rows=2048,
    plan_shards=4, plan_shard_rows=1_000_000, plan_groups=8,
    attn_seq=2048, attn_hd=128, train_steps=3, train_seq=256,
    multi_map_rows=64_000_000,
)
# Rehearsal (guide §2 step 1): same control flow, tiny data.
TINY = dict(
    map_chain=5,
    red_blocks=4, red_block_rows=1_024, red_dim=4,
    stream_chunks=4, stream_chunk_rows=2_048,
    agg_rows=20_000, agg_dim=8, agg_keys=(16, 300),
    mlp_sizes=(32, 32, 32, 10),
    inception_hw=75, inception_images=4,
    serve_clients=8, serve_rows=64,
    plan_shards=4, plan_shard_rows=4_096, plan_groups=8,
    attn_seq=256, attn_hd=32, train_steps=3, train_seq=64,
    multi_map_rows=40_000,
)


# ---------------------------------------------------------------------------
# the TensorFlow freeze child (never starts a JAX backend, never sees the chip)
# ---------------------------------------------------------------------------


def _freeze_child(out_dir: str, hw: int, images: int, seed: int) -> int:
    """Freeze the seeded Keras Inception-v3, score seeded images with the
    TF session, leave graph bytes + images + scores under ``out_dir``."""
    sys.path.insert(0, HERE)
    from tensorframes_tpu.testing.keras_freeze import freeze_keras_inception_v3

    wire, in_node, out_node, score = freeze_keras_inception_v3(hw)
    data = np.random.RandomState(seed).rand(images, hw, hw, 3).astype(np.float32)
    with open(os.path.join(out_dir, "inception_v3.pb"), "wb") as f:
        f.write(wire)
    np.save(os.path.join(out_dir, "images.npy"), data)
    np.save(os.path.join(out_dir, "tf_scores.npy"), score(data))
    with open(os.path.join(out_dir, "nodes.json"), "w") as f:
        json.dump({"in": in_node, "out": out_node}, f)
    # TensorFlow's own import pulls in the jax MODULE (tensorflow.lite
    # imports jax.jit); what must never happen here is a JAX backend
    # coming up, because that is what would reach for the chip
    if "jax" in sys.modules:
        from jax._src import xla_bridge

        assert not xla_bridge.backends_are_initialized(), (
            "the freeze child initialised a JAX backend"
        )
    return 0


# ---------------------------------------------------------------------------
# run state: phase accounting shared by every phase
# ---------------------------------------------------------------------------


class Run:
    def __init__(self, args, jax, tfs):
        self.jax, self.tfs = jax, tfs
        self.rehearse = args.rehearse
        self.size = TINY if args.rehearse else REAL
        self.seed = args.seed
        self.out_dir = args.out
        self.platform = jax.devices()[0].platform
        self.events = []  # (name, seconds) from jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(
            lambda name, secs, **kw: self.events.append((name, secs))
        )
        jax.monitoring.register_event_listener(
            lambda name, **kw: self.events.append((name, None))
        )

    def rng(self, salt: int):
        return np.random.RandomState(self.seed * 1000 + salt)

    def on_device(self, *arrays):
        """Assert each output is a jax.Array living only on devices of
        the platform under test; return the device names."""
        names = set()
        for a in arrays:
            assert isinstance(a, self.jax.Array), f"host value {type(a)}"
            for d in a.devices():
                assert d.platform == self.platform, (
                    f"output on {d} — expected a {self.platform} device"
                )
                names.add(str(d))
        return sorted(names)

    @contextlib.contextmanager
    def phase(self, name: str):
        """Time one phase and print its JSON line. A failing phase
        prints its line too (marked ``failed``) and the exception goes
        on up: it ends the run."""
        from tensorframes_tpu.utils import inspection, telemetry

        telemetry.reset()
        n0 = len(self.events)
        jit0 = inspection.executor_stats()["jit_shape_compiles"]
        info = {"phase": name}
        t0 = time.perf_counter()
        failed = True
        try:
            yield info
            failed = False
        finally:  # say what was seen; an exception still ends the run
            self._report(info, failed, t0, n0, jit0)

    def _report(self, info, failed, t0, n0, jit0):
        from tensorframes_tpu.utils import inspection, telemetry

        if failed:
            info["failed"] = True
        info["seconds"] = round(time.perf_counter() - t0, 3)
        ev = self.events[n0:]
        compile_ev = "/jax/core/compile/backend_compile_duration"
        info["compile_seconds"] = round(
            sum(s for n, s in ev if n == compile_ev), 3
        )
        info["xla_compiles"] = sum(1 for n, _ in ev if n == compile_ev)
        info["cache_hits"] = sum(
            1 for n, _ in ev if n == "/jax/compilation_cache/cache_hits"
        )
        info["jit_compiles"] = (
            inspection.executor_stats()["jit_shape_compiles"] - jit0
        )
        stats = self.jax.devices()[0].memory_stats() or {}
        # the peak is the process's so far, not this phase's alone
        info["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
        info["bytes_limit"] = stats.get("bytes_limit")
        info["spans"] = sorted({s.name for s in telemetry.spans()})
        print(json.dumps(info), flush=True)


# ---------------------------------------------------------------------------
# phases a–i (one chip)
# ---------------------------------------------------------------------------


def phase_a_readme(run: Run):
    """README "The five verbs" example, verbatim: float64 columns under
    the x64 mode the package enables."""
    tfs = run.tfs
    from tensorframes_tpu import dsl

    with run.phase("a_readme") as info:
        df = tfs.TensorFrame.from_dict({"x": np.array([1.0, 2.0, 3.0])})

        x = tfs.block(df, "x")                 # placeholder from column metadata
        z = (x + 3.0).named("z")
        df2 = tfs.map_blocks(z, df)            # -> columns [z, x]

        x_input = tfs.block(df, "x", tf_name="x_input")
        s = dsl.reduce_sum(x_input, axes=[0]).named("x")
        total = tfs.reduce_blocks(s, df)       # -> 6.0

        assert df2.columns == ["z", "x"]
        zv = df2["z"].values
        info["devices"] = run.on_device(zv, total)
        assert zv.dtype == np.float64
        np.testing.assert_array_equal(np.asarray(zv), [4.0, 5.0, 6.0])
        assert float(total) == 6.0


def _sum_min_fetches(tfs, df, col):
    from tensorframes_tpu import dsl

    xin = tfs.block(df, col, tf_name=f"{col}_input")
    return (
        dsl.reduce_sum(xin, axes=[0]).named(col),
        dsl.reduce_min(xin, axes=[0]).named(col),
    )


def _sparse_ints(rng, shape):
    """Integer-valued float32 data, ~5% non-zero (1..3), so that a
    float32 sum over tens of millions of rows stays below 2^24 and is
    exact in any summation order; a few negative spikes make the min
    non-trivial."""
    draw = rng.randint(0, 64, size=shape, dtype=np.uint8)
    a = np.where(draw < 3, draw + 1, 0).astype(np.float32)
    flat = a.reshape(len(a), -1)
    for j in range(flat.shape[1]):
        flat[rng.randint(0, len(a), size=3), j] = -(j + 2.0)
    return a


def phase_c_reduce(run: Run):
    """`reduce_blocks` sum and min over a many-block vector frame, and
    `reduce_blocks_stream` over host chunks (the north star in small)."""
    tfs, sz = run.tfs, run.size
    rng = run.rng(3)
    blocks, brows, dim = sz["red_blocks"], sz["red_block_rows"], sz["red_dim"]
    with run.phase("c_reduce_blocks") as info:
        host = _sparse_ints(rng, (blocks * brows, dim))
        df = tfs.TensorFrame.from_dict({"v": host}, num_blocks=blocks).to_device()
        s, mn = _sum_min_fetches(tfs, df, "v")
        total = tfs.reduce_blocks(s, df)
        low = tfs.reduce_blocks(mn, df)
        info["devices"] = run.on_device(total, low)
        ref_sum = host.sum(axis=0, dtype=np.float64)
        assert ref_sum.max() < 2**24
        np.testing.assert_array_equal(np.asarray(total), ref_sum)
        np.testing.assert_array_equal(np.asarray(low), host.min(axis=0))
        info["rows"], info["blocks"] = blocks * brows, blocks
        del df

    chunks, crows = sz["stream_chunks"], sz["stream_chunk_rows"]
    with run.phase("c_reduce_stream") as info:
        parts = [_sparse_ints(rng, (crows,)) for _ in range(chunks)]
        probe = tfs.TensorFrame.from_dict({"x": parts[0][:4]})
        s, _ = _sum_min_fetches(tfs, probe, "x")
        total = tfs.reduce_blocks_stream(
            s, (tfs.TensorFrame.from_dict({"x": p}) for p in parts)
        )
        info["devices"] = run.on_device(total)
        ref = sum(p.sum(dtype=np.float64) for p in parts)
        assert ref < 2**24
        assert float(total) == ref
        info["rows"], info["chunks"] = chunks * crows, chunks


def _mean_var_by_key(tfs, df, sums=False, **verb_kw):
    """Keyed mean + variance (BASELINE config 4's shape): squares via
    `map_blocks`, then ONE keyed `aggregate`. Returns per-key (keys,
    E[v], E[v^2]). Two formulations: keyed Means, or — ``sums=True``,
    the associative form of tests/test_parallel.py's
    test_mean_variance_meshed, which the `mesh=` path lowers to
    `segment_sum` + `psum` — keyed Sums of v, v^2 and a count column."""
    from tensorframes_tpu import dsl

    red = dsl.reduce_sum if sums else dsl.reduce_mean
    sq = tfs.map_blocks(
        lambda v: {"vsq": v * v, "cnt": v[:, :1] * 0 + 1}, df, **verb_kw
    )
    fetches = [
        red(tfs.block(sq, c, tf_name=f"{c}_input"), axes=[0]).named(c)
        for c in (("v", "vsq", "cnt") if sums else ("v", "vsq"))
    ]
    out = tfs.aggregate(fetches, tfs.group_by(sq, "k"), **verb_kw)
    cols = {c: np.asarray(out[c].values, np.float64) for c in ("v", "vsq")}
    if sums:
        cnt = np.asarray(out["cnt"].values, np.float64)
        cols = {c: a / cnt for c, a in cols.items()}
    return out, np.asarray(out["k"].host_values()), cols["v"], cols["vsq"]


def _mean_var_reference(keys, data, nkeys):
    """float64 numpy reference: per-key mean and variance per column."""
    cnt = np.bincount(keys, minlength=nkeys).astype(np.float64)[:, None]
    d = data.astype(np.float64)
    s1 = np.stack(
        [np.bincount(keys, d[:, j], nkeys) for j in range(d.shape[1])], 1
    )
    s2 = np.stack(
        [np.bincount(keys, d[:, j] ** 2, nkeys) for j in range(d.shape[1])], 1
    )
    mean = s1 / cnt
    return mean, s2 / cnt - mean**2


# float32 accumulation over <= 625,000 rows per key against a float64
# reference. On the chip the one-hot MXU branch (16 keys) lands 2.2e-4
# below float64 in every mean — 1e-4 did not hold there (CHANGES.md PR
# 22); the variance loses more digits to E[x^2] - E[x]^2 cancellation
AGG_MEAN_RTOL, AGG_VAR_RTOL = 1e-3, 1e-2


def _check_mean_var(result, keys, data, nkeys, record):
    """Compare with float64; ``record`` gets the errors seen BEFORE the
    tolerance is asserted, so a failing phase line still carries them."""
    _, out_keys, mean, ex2 = result
    order = np.argsort(out_keys)
    mean, var = mean[order], ex2[order] - mean[order] ** 2
    ref_mean, ref_var = _mean_var_reference(keys, data, nkeys)
    record["mean_max_rel_err"] = float(np.max(np.abs(mean / ref_mean - 1)))
    record["var_max_rel_err"] = float(np.max(np.abs(var / ref_var - 1)))
    np.testing.assert_allclose(mean, ref_mean, rtol=AGG_MEAN_RTOL)
    np.testing.assert_allclose(var, ref_var, rtol=AGG_VAR_RTOL)


def _aggregate_branch(nkeys: int) -> str:
    """Which segment program `aggregate` built for this key count: its
    executor-cache kind ends in 1 for the one-hot MXU matmul and in 0
    for `segment_sum` (`aggregate.py`)."""
    from tensorframes_tpu.runtime.executor import default_executor

    kinds = {
        k[0] for k in default_executor().cache_keys()
        if str(k[0]).startswith(f"segagg-{nkeys}-")
    }
    assert len(kinds) == 1, kinds
    return "onehot" if kinds.pop().endswith("-1") else "segment"


def phase_d_aggregate(run: Run):
    tfs, sz = run.tfs, run.size
    rows, dim = sz["agg_rows"], sz["agg_dim"]
    rng = run.rng(4)
    data = rng.rand(rows, dim).astype(np.float32)
    for nkeys in sz["agg_keys"]:
        with run.phase(f"d_aggregate_{nkeys}_keys") as info:
            keys = rng.randint(0, nkeys, size=rows).astype(np.int32)
            df = tfs.TensorFrame.from_dict({"k": keys, "v": data}).to_device()
            res = _mean_var_by_key(tfs, df)
            info["devices"] = run.on_device(
                res[0]["v"].values, res[0]["vsq"].values
            )
            info["branch"] = _aggregate_branch(nkeys)
            info["tolerance"] = {
                "mean_rtol": AGG_MEAN_RTOL, "var_rtol": AGG_VAR_RTOL
            }
            _check_mean_var(res, keys, data, nkeys, info)
            if not run.rehearse:
                # <= 256 keys and rows x keys <= 2^28: the TPU-only branch
                assert info["branch"] == (
                    "onehot" if nkeys <= 256 else "segment"
                ), info["branch"]
            info["rows"], info["keys"] = rows, nkeys


# the tolerance of tests/test_foreign_graphdef.py; it held on the chip
# (max abs error 1.4e-9, CHANGES.md PR 22), so no wider one is offered
INCEPTION_RTOL, INCEPTION_ATOL = 1e-4, 1e-5


def phase_f_inception(run: Run):
    """Frozen Keras Inception-v3 GraphDef scored through `map_blocks`."""
    tfs, jax, sz = run.tfs, run.jax, run.size
    hw, images = sz["inception_hw"], sz["inception_images"]
    with run.phase("f_inception_v3") as info:
        t0 = time.perf_counter()
        # TensorFlow lives in a child that never starts a JAX backend and
        # is gone before its files are read: it cannot take the chip
        subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--freeze-child",
             run.out_dir, str(hw), str(images), str(run.seed)],
            check=True, timeout=900,
        )
        info["freeze_and_tf_score_seconds"] = round(time.perf_counter() - t0, 3)
        with open(os.path.join(run.out_dir, "inception_v3.pb"), "rb") as f:
            wire = f.read()
        with open(os.path.join(run.out_dir, "nodes.json")) as f:
            nodes = json.load(f)
        data = np.load(os.path.join(run.out_dir, "images.npy"))
        tf_scores = np.load(os.path.join(run.out_dir, "tf_scores.npy"))

        df = tfs.TensorFrame.from_dict({"images": data}).to_device()
        out = tfs.map_blocks(
            wire, df, fetch_names=[nodes["out"]],
            feed_dict={nodes["in"]: "images"}, trim=True,
        )
        scores = out[nodes["out"]].values
        jax.block_until_ready(scores)
        info["devices"] = run.on_device(scores)
        ours = np.asarray(scores)
        assert ours.shape == tf_scores.shape == (images, 1000)
        assert np.isfinite(ours).all()
        info["max_abs_err"] = float(np.max(np.abs(ours - tf_scores)))
        info["top1_agree"] = float(
            np.mean(ours.argmax(1) == tf_scores.argmax(1))
        )
        info["tolerance"] = {"rtol": INCEPTION_RTOL, "atol": INCEPTION_ATOL}
        np.testing.assert_allclose(
            ours, tf_scores, rtol=INCEPTION_RTOL, atol=INCEPTION_ATOL
        )
        info["graph_bytes"], info["images"], info["px"] = len(wire), images, hw


def _serve_round(run: Run, url, name, graph, reqs, out_col):
    """One request per concurrent client against endpoint ``name``;
    every answer is compared with a direct `map_blocks` of the same
    rows. Returns (bit-identical?, max |difference|)."""
    tfs = run.tfs
    answers, errors = [None] * len(reqs), []
    barrier = threading.Barrier(len(reqs))

    def client(i):
        try:
            barrier.wait(timeout=60)
            out = tfs.serving.ServingClient(url).run(
                name, reqs[i], timeout_s=120.0
            )
            answers[i] = np.asarray(out[out_col].host_values())
        except BaseException as e:  # re-raised on the main thread
            errors.append(e)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(reqs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not any(t.is_alive() for t in threads)
    if errors:
        raise errors[0]
    worst, identical = 0.0, True
    for req, answer in zip(reqs, answers):
        direct = tfs.map_blocks(graph, tfs.TensorFrame.from_dict(req))
        direct = direct[out_col].values
        run.on_device(direct)
        direct = np.asarray(direct)
        np.testing.assert_allclose(answer, direct, rtol=1e-5, atol=1e-7)
        identical &= np.array_equal(answer, direct)
        worst = max(worst, float(np.max(np.abs(answer - direct))))
    return bool(identical), worst


def phase_g_serving(run: Run):
    """`tfs.serving` behind the HTTP front-end, concurrent clients. Two
    endpoints, because the batcher coalesces only graphs its row-local
    walk can prove (`aggregate._ROWWISE_OPS` has no MatMul): the MLP
    scoring graph is served per request, and an elementwise scorer shows
    the micro-batcher coalescing on the device."""
    tfs, sz = run.tfs, run.size
    import socket

    from tensorframes_tpu import dsl
    from tensorframes_tpu.models import MLP
    from tensorframes_tpu.schema import ScalarType, Shape

    clients, rows = sz["serve_clients"], sz["serve_rows"]
    width = sz["mlp_sizes"][0]
    with run.phase("g_serving") as info:
        model = MLP(list(sz["mlp_sizes"]), seed=run.seed)
        mlp = model.scoring_graph("features", block=True)
        x = dsl.placeholder(ScalarType.float32, shape=Shape((None,)), name="x")
        two, one = (dsl.constant(np.float32(c)) for c in (2.0, 1.0))
        affine = ((((x * two) + one) * ((x * x) + two)) + one).named("score")
        ep_mlp = tfs.serving.register(
            "mlp", mlp, {"features": ("float32", (width,))},
            max_batch_rows=rows * clients,
        )
        ep_aff = tfs.serving.register(
            "affine", affine, {"x": "float32"}, max_batch_rows=rows * clients
        )
        info["batchable"] = {"mlp": ep_mlp.batchable, "affine": ep_aff.batchable}
        assert ep_aff.batchable
        handle = tfs.serving.serve(port=0)
        port = handle.port
        try:
            rng = run.rng(7)
            ident, worst = _serve_round(
                run, handle.url, "mlp", mlp,
                [{"features": rng.rand(rows, width).astype(np.float32)}
                 for _ in range(clients)],
                "probs",
            )
            info["mlp_bit_identical_to_direct_map_blocks"] = ident
            info["mlp_max_abs_diff"] = worst
            before = tfs.serving.batcher().snapshot()
            # Sizes just off a bucket rung: a batch that lands exactly
            # on a rung closes at once by design (2,048 rows is one).
            # And a window wider than the default 5 ms: eight Python
            # client threads reach the server tens of ms apart, and this
            # phase shows coalescing on the device, not a latency.
            with tfs.config.override(serve_batch_window_ms=250.0):
                ident, worst = _serve_round(
                    run, handle.url, "affine", affine,
                    [{"x": rng.rand(rows - 1 - i % 7).astype(np.float32)}
                     for i in range(clients)],
                    "score",
                )
            info["affine_bit_identical_to_direct_map_blocks"] = ident
            info["affine_max_abs_diff"] = worst
            snap = tfs.serving.batcher().snapshot()
            info["requests_per_endpoint"] = clients
            info["batcher"] = snap
            batches = snap["batches"] - before["batches"]
            assert snap["batched_requests"] - before["batched_requests"] == clients, snap
            assert 0 < batches < clients, snap  # coalesced
            info["affine_batches"] = batches
        finally:
            tfs.telemetry.shutdown()
            tfs.serving.reset()
        with socket.socket() as s:  # shutdown() freed the port
            assert s.connect_ex(("127.0.0.1", port)) != 0
        info["port_freed"] = True


def phase_h_plan(run: Run):
    """A PR 20 plan: scan(parquet) -> filter -> map_blocks -> group_by
    -> agg, against pandas."""
    tfs, sz = run.tfs, run.size
    import pandas as pd

    from tensorframes_tpu import col, dsl
    from tensorframes_tpu import io as tio
    from tensorframes_tpu.graph import plan as planmod
    from tensorframes_tpu.schema import ScalarType, Shape

    shards, srows, groups = (
        sz["plan_shards"], sz["plan_shard_rows"], sz["plan_groups"]
    )
    root = os.path.join(run.out_dir, "plan_dataset")
    os.makedirs(root, exist_ok=True)
    with run.phase("h_plan") as info:
        rng = run.rng(8)
        frames = []
        for i in range(shards):
            # x ascends through 0..63 within a shard, so row-group
            # min/max stats can prune; integer values keep float32 exact
            x = np.floor(np.arange(srows) * (64.0 / srows)).astype(np.float32)
            y = rng.randint(0, 16, size=srows).astype(np.float32)
            w = rng.rand(srows).astype(np.float32)  # dead weight to prune
            frames.append(pd.DataFrame({"x": x, "y": y, "w": w}))
            tio.write_parquet(
                tfs.TensorFrame.from_dict(
                    {"x": x, "y": y, "w": w}, num_blocks=groups
                ),
                os.path.join(root, f"shard-{i:04d}.parquet"),
            )
        planmod.reset_state()
        ph = dsl.placeholder(ScalarType.float32, Shape((None,)), name="x")
        z = (ph * np.float32(0.5) + np.float32(1.0)).named("z")
        out = (
            tfs.scan(root)
            .filter(col("x") > 47.0)
            .map_blocks(z, feed_dict={"x": "x"})
            .group_by("y")
            .agg(z_sum=("sum", "z"), z_max=("max", "z"))
            .force()
        )
        info["devices"] = run.on_device(
            out["z_sum"].values, out["z_max"].values
        )
        got = out.to_pandas().sort_values("y").reset_index(drop=True)
        full = pd.concat(frames)
        kept = full[full.x > 47.0].assign(z=lambda d: d.x * 0.5 + 1.0)
        ref = (
            kept.groupby("y").z.agg(["sum", "max"]).reset_index()
            .sort_values("y").reset_index(drop=True)
        )
        np.testing.assert_array_equal(got["y"], ref["y"])
        np.testing.assert_array_equal(got["z_sum"], ref["sum"])
        np.testing.assert_array_equal(got["z_max"], ref["max"])
        st = planmod.state()
        info["plan_fallbacks"] = st["fallbacks"]
        info["plan_pushdown_rows_skipped"] = st["pushdown_rows_skipped"]
        info["plan_rewrites"] = st["rewrites"]
        assert st["pushdown_rows_skipped"] > 0, st
        info["rows"], info["rows_kept"] = shards * srows, len(kept)


def _numpy_attention(q, k, v, causal=True):
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    s = q @ k.T / np.sqrt(q.shape[-1])
    if causal:
        s = np.where(np.tril(np.ones_like(s, bool)), s, -np.inf)
    w = np.exp(s - s.max(axis=-1, keepdims=True))
    return (w / w.sum(axis=-1, keepdims=True)) @ v


# max |kernel - float64 reference| allowed: the kernel feeds the MXU
# float32 operands at the chip's default (bf16-pass) precision, and a
# bfloat16 output rounds at 2^-9 relative
ATTN_ATOL = {"float32": 2e-2, "bfloat16": 4e-2}


def phase_i_kernel_and_train(run: Run):
    jax, sz = run.jax, run.size
    import jax.numpy as jnp

    from tensorframes_tpu.models import TransformerLM
    from tensorframes_tpu.ops.pallas_kernels import flash_attention
    from tensorframes_tpu.parallel.ring import full_attention

    seq, hd = sz["attn_seq"], sz["attn_hd"]
    # compiled for the chip; interpreted ONLY in the rehearsal, and the
    # phase line says which
    interpret = run.rehearse
    for dtype in (jnp.float32, jnp.bfloat16):
        name = jnp.dtype(dtype).name
        with run.phase(f"i_flash_attention_{name}") as info:
            rng = run.rng(9)
            q, k, v = (
                jnp.asarray(rng.randn(seq, hd), dtype) for _ in range(3)
            )
            fn = jax.jit(functools.partial(
                flash_attention, causal=True, interpret=interpret
            ))
            has_kernel = "tpu_custom_call" in fn.lower(q, k, v).as_text()
            assert has_kernel or interpret, "no Mosaic kernel in the program"
            out = fn(q, k, v)
            jax.block_until_ready(out)
            info["devices"] = run.on_device(out)
            assert out.shape == (seq, hd) and out.dtype == dtype
            got = np.asarray(out.astype(jnp.float32), np.float64)
            ref = _numpy_attention(*(np.asarray(a.astype(jnp.float32))
                                     for a in (q, k, v)))
            with jax.default_matmul_precision("highest"):
                repo_ref = np.asarray(full_attention(
                    *(a.astype(jnp.float32) for a in (q, k, v)), causal=True
                ), np.float64)
            info["max_abs_err_vs_numpy"] = float(np.max(np.abs(got - ref)))
            info["max_abs_err_vs_full_attention"] = float(
                np.max(np.abs(got - repo_ref))
            )
            info["atol"] = ATTN_ATOL[name]
            assert info["max_abs_err_vs_numpy"] <= ATTN_ATOL[name], info
            assert info["max_abs_err_vs_full_attention"] <= ATTN_ATOL[name], info
            info["interpret"], info["tpu_custom_call"] = interpret, has_kernel
            info["shape"], info["causal"] = [seq, hd], True

    with run.phase("i_transformer_train_step") as info:
        lm = TransformerLM()  # its default sizes
        tokens = jnp.asarray(
            run.rng(10).randint(0, lm.vocab, size=sz["train_seq"] + 1), jnp.int32
        )
        step = jax.jit(lm.train_step)
        params, losses = lm.params, []
        for _ in range(sz["train_steps"]):
            params, loss = step(params, tokens)
            losses.append(float(loss))
        info["devices"] = run.on_device(loss, *params.values())
        assert all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0], losses  # SGD on one batch descends
        info["losses"] = losses
        # on a TPU the step runs the kernel forward and full_attention's
        # VJP backward (the kernel's custom_vjp)
        info["attention"] = (
            "flash_attention kernel" if jax.default_backend() == "tpu"
            else "full_attention"
        )
        info["sizes"] = {
            "vocab": lm.vocab, "d_model": lm.d_model, "n_heads": lm.n_heads,
            "n_layers": lm.n_layers, "seq": sz["train_seq"],
        }


# ---------------------------------------------------------------------------
# --chips 4: the several-chip path and what it is compared with
# ---------------------------------------------------------------------------


def _four_shards(run: Run, arr, ndev: int):
    """A sharded column: ndev addressable shards on ndev distinct devices."""
    run.on_device(arr)
    shards = arr.addressable_shards
    assert len(shards) == ndev, len(shards)
    assert len({s.device for s in shards}) == ndev
    return sorted(str(s.device) for s in shards)


def _dispatch_devices() -> dict:
    from tensorframes_tpu.utils import inspection

    return dict(inspection.executor_stats().get("device_dispatches", {}))


def phase_multichip(run: Run, ndev: int):
    tfs, jax, sz = run.tfs, run.jax, run.size
    from tensorframes_tpu.frame import Column
    from tensorframes_tpu.parallel import data_mesh

    devs = jax.local_devices()
    assert len(devs) == ndev, f"--chips {ndev} needs {ndev} devices: {devs}"
    one = [devs[0]]
    mesh = data_mesh()
    assert mesh.devices.size == ndev

    def scheduled_over_all(before):
        used = {
            d: n - before.get(d, 0)
            for d, n in _dispatch_devices().items()
            if n - before.get(d, 0) > 0
        }
        assert len(used) > 1, f"scheduler used only {used}"
        return used

    # -- phase b at 4 blocks: chained x+3 ------------------------------
    n, chain = sz["multi_map_rows"], sz["map_chain"]
    with run.phase("multi_map_chain") as info:
        host = (np.arange(n, dtype=np.int64) % 1024).astype(np.float32)
        want = host + np.float32(3.0 * chain)
        src = tfs.TensorFrame.from_dict({"x": host}, num_blocks=ndev)
        z = (tfs.block(src, "x") + 3.0).named("z")

        def chained(df, rebuild, **kw):
            for _ in range(chain):
                df = rebuild(tfs.map_blocks(z, df, **kw))
            return df

        def as_frame(out):
            return tfs.TensorFrame([Column("x", out["z"].values)], out.offsets)

        def as_global(out):
            return tfs.GlobalFrame(
                [Column("x", out.column("z").values)], mesh, n
            )

        single = chained(src.to_device(device=devs[0]), as_frame, devices=one)
        np.testing.assert_array_equal(np.asarray(single["x"].values), want)
        assert len(single["x"].values.devices()) == 1

        before = _dispatch_devices()
        sched = chained(src, as_frame)  # block scheduler, jax.local_devices()
        np.testing.assert_array_equal(np.asarray(sched["x"].values), want)
        info["scheduler_dispatches"] = scheduled_over_all(before)

        meshed = chained(src.to_device(mesh), as_frame, mesh=mesh)
        info["mesh_shards"] = _four_shards(run, meshed["x"].values, ndev)
        np.testing.assert_array_equal(np.asarray(meshed["x"].values), want)

        glob = chained(src.to_global(mesh), as_global)
        info["global_shards"] = _four_shards(run, glob.column("x").values, ndev)
        np.testing.assert_array_equal(glob.host_values("x"), want)
        info["rows"], info["blocks"] = n, ndev

    # -- phase c's reduce_blocks ---------------------------------------
    blocks, brows, dim = sz["red_blocks"], sz["red_block_rows"], sz["red_dim"]
    with run.phase("multi_reduce_blocks") as info:
        host = _sparse_ints(run.rng(3), (blocks * brows, dim))
        ref_sum = host.sum(axis=0, dtype=np.float64)
        ref_min = host.min(axis=0)
        src = tfs.TensorFrame.from_dict({"v": host}, num_blocks=blocks)
        s, mn = _sum_min_fetches(tfs, src, "v")

        def check(frame, **kw):
            total = tfs.reduce_blocks(s, frame, **kw)
            low = tfs.reduce_blocks(mn, frame, **kw)
            run.on_device(total, low)
            np.testing.assert_array_equal(np.asarray(total), ref_sum)
            np.testing.assert_array_equal(np.asarray(low), ref_min)

        check(src.to_device(device=devs[0]), devices=one)
        before = _dispatch_devices()
        check(src)
        info["scheduler_dispatches"] = scheduled_over_all(before)
        sharded = src.to_device(mesh)
        info["mesh_shards"] = _four_shards(run, sharded["v"].values, ndev)
        check(sharded, mesh=mesh)
        glob = src.to_global(mesh)
        info["global_shards"] = _four_shards(run, glob.column("v").values, ndev)
        check(glob)
        info["rows"], info["blocks"] = blocks * brows, blocks

    # -- phase d: keyed mean + variance --------------------------------
    rows, dim = sz["agg_rows"], sz["agg_dim"]
    rng = run.rng(4)
    data = rng.rand(rows, dim).astype(np.float32)
    for nkeys in sz["agg_keys"]:
        with run.phase(f"multi_aggregate_{nkeys}_keys") as info:
            keys = rng.randint(0, nkeys, size=rows).astype(np.int32)
            src = tfs.TensorFrame.from_dict(
                {"k": keys, "v": data}, num_blocks=ndev
            )
            errs = info["max_rel_err"] = {}

            def check(label, frame, **kw):
                res = _mean_var_by_key(tfs, frame, sums=True, **kw)
                if "mesh" not in kw:
                    run.on_device(res[0]["v"].values)
                else:
                    # the mesh segment path fetches its psum'd per-key
                    # table to fold the tail in on the host
                    # (parallel/verbs.py): a host array, said here
                    info["mesh_output"] = type(res[0]["v"].values).__name__
                errs[label] = {}
                _check_mean_var(res, keys, data, nkeys, errs[label])

            check("one_device", src.to_device(device=devs[0]), devices=one)
            before = _dispatch_devices()
            check("scheduler", src)
            info["scheduler_dispatches"] = scheduled_over_all(before)
            # a device-resident frame (committed to device 0) handed to a
            # mesh= verb: the pattern test_mean_variance_meshed pins
            check("mesh_from_one_device", src.to_device(device=devs[0]), mesh=mesh)
            sharded = src.to_device(mesh)
            info["mesh_shards"] = _four_shards(run, sharded["v"].values, ndev)
            check("mesh", sharded, mesh=mesh)
            info["tolerance"] = {
                "mean_rtol": AGG_MEAN_RTOL, "var_rtol": AGG_VAR_RTOL
            }
            info["rows"], info["keys"] = rows, nkeys


# ---------------------------------------------------------------------------

ONE_CHIP_PHASES = {
    "a": phase_a_readme,
    "c": phase_c_reduce,
    "d": phase_d_aggregate,
    "f": phase_f_inception,
    "g": phase_g_serving,
    "h": phase_h_plan,
    "i": phase_i_kernel_and_train,
}
PHASES = "".join(ONE_CHIP_PHASES)


def main(argv=None) -> int:
    if len(sys.argv) > 1 and sys.argv[1] == "--freeze-child":
        out_dir, hw, images, seed = sys.argv[2:6]
        return _freeze_child(out_dir, int(hw), int(images), int(seed))

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes; a CPU is accepted; never prints ok")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(HERE, ".chip_smoke"),
                    help="directory for the datasets and graphs the run writes")
    ap.add_argument("--phases", default=PHASES,
                    help=f"one-chip phases to run, default {PHASES}; a "
                         "subset is for finding faults and never prints ok")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke.py needs a TPU; jax found {device}", file=sys.stderr)
        return 1

    sys.path.insert(0, HERE)
    import tensorframes_tpu as tfs
    from tensorframes_tpu import native

    os.makedirs(args.out, exist_ok=True)
    run = Run(args, jax, tfs)
    t0 = time.perf_counter()
    print(json.dumps({
        "phase": "start", "device": device, "jax": jax.__version__,
        "x64": bool(jax.config.jax_enable_x64), "rehearse": args.rehearse,
        "seed": args.seed,
        "compilation_cache_dir": tfs.config.enable_compilation_cache(),
        # pure-Python GraphDef parse / ragged kernels when False; this
        # script builds nothing
        "native.available": native.available(),
    }), flush=True)

    if args.chips == 4:
        phase_multichip(run, 4)
    else:
        for letter in args.phases:
            ONE_CHIP_PHASES[letter](run)

    total = {"phase": "total", "seconds": round(time.perf_counter() - t0, 3)}
    if args.rehearse or dev.platform != "tpu" or args.phases != PHASES:
        # a rehearsal is not a chip run: no success line, whatever ran
        print(json.dumps({**total, "rehearsal": "passed", "device": device}))
        return 0
    print(json.dumps(total), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
