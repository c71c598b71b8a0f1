"""Shape-bucketed block execution (ISSUE 3): bounded XLA recompiles.

The contract under test: with `config.shape_bucketing` on (the default),
any workload's distinct compiled SHAPES per program stay on the bucket
ladder — O(log max-block-rows) — no matter how block sizes drift, and
results match unbucketed eager execution (bit-identical for map outputs,
min/max, integer dtypes, and integer-valued float data; the documented
FP-reassociation tolerance otherwise). Graphs the classifiers cannot
prove safe (non-row-local maps, non-monoid reduces) run the exact
unbucketed dispatch regardless of the knob.
"""

import logging
import math

import numpy as np
import pytest

import tensorframes_tpu as tfs
from tensorframes_tpu import dsl
from tensorframes_tpu import shape_policy as sp
from tensorframes_tpu.runtime.executor import Executor
from tensorframes_tpu.utils.inspection import executor_stats


def _uneven(sizes, mod=13, dtype=np.float32):
    """One float column with integer-valued data (order-independent-exact
    FP sums) split into blocks of the given sizes."""
    n = int(sum(sizes))
    offsets = list(np.cumsum([0] + list(sizes)))
    df = tfs.TensorFrame.from_dict({"x": (np.arange(n) % mod).astype(dtype)})
    return tfs.TensorFrame([df["x"]], offsets)


def _reduce(df_like, op, col="x"):
    ph = tfs.block(df_like, col, tf_name=col + "_input")
    return {
        "sum": dsl.reduce_sum,
        "min": dsl.reduce_min,
        "max": dsl.reduce_max,
        "mean": dsl.reduce_mean,
    }[op](ph, axes=[0]).named(col)


class TestBucketLadder:
    def test_ladder_is_geometric_and_monotone(self):
        with tfs.config.override(shape_bucket_growth=2.0, shape_bucket_min=8):
            assert sp.bucket_for(0) == 0
            assert sp.bucket_for(1) == 8
            assert sp.bucket_for(8) == 8
            assert sp.bucket_for(9) == 16
            assert sp.bucket_for(1000) == 1024
            ladder = sp.bucket_ladder(1000)
            assert ladder == [8, 16, 32, 64, 128, 256, 512, 1024]

    def test_growth_factor_configurable(self):
        with tfs.config.override(shape_bucket_growth=4.0, shape_bucket_min=4):
            assert sp.bucket_ladder(200) == [4, 16, 64, 256]
        with tfs.config.override(shape_bucket_growth=1.5, shape_bucket_min=8):
            ladder = sp.bucket_ladder(100)
            assert ladder[0] == 8 and ladder[-1] >= 100
            assert all(b < a for b, a in zip(ladder, ladder[1:]))

    def test_bad_geometry_raises(self):
        with tfs.config.override(shape_bucket_growth=1.0):
            with pytest.raises(ValueError, match="shape_bucket_growth"):
                sp.bucket_for(5)
        with tfs.config.override(shape_bucket_min=0):
            with pytest.raises(ValueError, match="shape_bucket_min"):
                sp.bucket_for(5)

    def test_frame_bucketed_block_sizes(self):
        df = _uneven([5, 0, 12, 40])
        with tfs.config.override(shape_bucket_growth=2.0, shape_bucket_min=8):
            assert df.bucketed_block_sizes() == [8, 0, 16, 64]
        assert df.block_sizes() == [5, 0, 12, 40]


class TestBucketedMap:
    def test_map_bit_identical_and_bounded_compiles(self):
        sizes = [3, 9, 17, 31, 64, 101, 7, 55]  # 8 distinct sizes
        df = _uneven(sizes)
        ex = Executor()
        # single-device compile economics: the block scheduler would
        # spread blocks over devices and jit once per (device, rung) —
        # the scheduler suite asserts that scaled bound; here it is off
        with tfs.config.override(block_scheduler="off"):
            out = tfs.map_blocks(
                (tfs.block(df, "x") * 2.0 + 1.0).named("y"), df, executor=ex
            )
        np.testing.assert_array_equal(
            np.asarray(out["y"].values), df["x"].values * 2.0 + 1.0
        )
        # one "block" program, shapes quantized to the ladder
        rungs = len(set(df.bucketed_block_sizes()))
        assert ex.jit_shape_compiles() <= rungs
        assert rungs < len(set(sizes))

    def test_map_unbucketed_compiles_one_per_size(self):
        sizes = [3, 9, 17, 31, 64, 101, 7, 55]
        df = _uneven(sizes)
        with tfs.config.override(shape_bucketing=False):
            ex = Executor()
            tfs.map_blocks(
                (tfs.block(df, "x") * 2.0 + 1.0).named("y"), df, executor=ex
            )
            assert ex.jit_shape_compiles() == len(set(sizes))

    def test_non_rowwise_map_not_bucketed_and_exact(self):
        # y = x - mean(x) depends on the WHOLE block: padding would
        # corrupt valid rows, so the classifier must refuse it
        df = _uneven([5, 12, 20])
        x = tfs.block(df, "x")
        y = (x - dsl.reduce_mean(x, axes=[0])).named("y")
        ex = Executor()
        out = tfs.map_blocks(y, df, executor=ex)
        want = np.concatenate(
            [
                df["x"].values[lo:hi] - df["x"].values[lo:hi].mean()
                for lo, hi in zip(df.offsets, df.offsets[1:])
            ]
        )
        np.testing.assert_allclose(np.asarray(out["y"].values), want, rtol=1e-5)
        # unbucketed: one jit specialization per distinct block size
        assert ex.jit_shape_compiles() == 3

    def test_rowwise_classifier(self):
        df = _uneven([4, 4])
        g1, f1 = dsl.build((tfs.block(df, "x") * 2.0).named("y"))
        from tensorframes_tpu.graph.analysis import analyze_graph

        s1 = analyze_graph(g1, f1)
        ranks = {p: ph.shape.rank for p, ph in s1.inputs.items()}
        assert sp.rowwise_fetches(g1, f1, ranks)
        x = tfs.block(df, "x")
        g2, f2 = dsl.build(dsl.reduce_sum(x, axes=[0]).named("y"))
        s2 = analyze_graph(g2, f2)
        ranks2 = {p: ph.shape.rank for p, ph in s2.inputs.items()}
        assert not sp.rowwise_fetches(g2, f2, ranks2)


# ---------------------------------------------------------------------------
# the block window (ISSUE 27): cut + pad of a resident column in one program
# ---------------------------------------------------------------------------


def _resident(cols, sizes):
    """A frame of device-resident columns cut into blocks of ``sizes``."""
    import jax

    from tensorframes_tpu.frame import Column

    offsets = [int(v) for v in np.cumsum([0] + list(sizes))]
    return tfs.TensorFrame(
        [Column(k, jax.device_put(v)) for k, v in cols.items()], offsets
    )


def _ints(n, width=None, mod=13):
    v = (np.arange(n * (width or 1)) % mod).astype(np.float32)
    return v.reshape(n, width) if width else v


def _two_columns(df):
    return (tfs.block(df, "x") * 2.0 + tfs.block(df, "y")).named("z")


def _times_two(df):
    return (tfs.block(df, "x") * 2.0).named("z")


def _rows_times_two(df):
    return (tfs.row(df, "x") * 2.0).named("z")


# case -> (verb, frame, fetch, injected fault, the (shift, n) pairs the
# unpad program must see, window dispatches, padded dispatches, sizes
# dispatched exact as their rung's first); rung ladder 8, 16, 32, ...
# No two neighbouring blocks share a size: a run of equal blocks on one
# device is one group (ISSUE 34, `TestBlockGroup`), not windows.
_WINDOW_CASES = {
    # every window starts at its block: 10, 11, 10 rows in rungs of 16,
    # the last block (16 rows) on its rung and so neither windowed nor
    # padded
    "interior-blocks": (
        "map_blocks", lambda: _resident({"x": _ints(47)}, [10, 11, 10, 16]),
        _times_two, None, {(0, 10), (0, 11)}, 3, 0, [],
    ),
    # the block at row 86 of 95 has no 16 rows after it: its window
    # starts at 79 and its rows sit 7 into it
    "last-blocks": (
        "map_blocks", lambda: _resident({"x": _ints(95)}, [10, 9] * 5),
        _times_two, None, {(0, 10), (0, 9), (7, 9)}, 10, 0, [],
    ),
    "two-feed-columns": (
        "map_blocks",
        lambda: _resident(
            {"x": _ints(60), "y": _ints(60, mod=7)}, [20, 19, 21]
        ),
        _two_columns, None, {(0, 20), (0, 19), (11, 21)}, 3, 0, [],
    ),
    "2d-column-map_rows": (
        "map_rows",
        lambda: _resident({"x": _ints(60, width=3)}, [20, 19, 21]),
        _rows_times_two, None, {(0, 20), (0, 19), (11, 21)}, 3, 0, [],
    ),
    # 64 is on its rung; 55 ends the column, 9 rows short of its rung
    "uneven-blocks": (
        "map_blocks",
        lambda: _resident({"x": _ints(287)}, [3, 9, 17, 31, 64, 101, 7, 55]),
        _times_two, None,
        {(0, 3), (0, 9), (0, 17), (0, 31), (0, 101), (0, 7), (9, 55)}, 7, 0, [],
    ),
    # block 0's first dispatch runs out of memory: its halves take
    # smaller windows of the same column (50 -> 25 + 25 rows)
    "oom-split-half": (
        "map_blocks", lambda: _resident({"x": _ints(99)}, [50, 49]),
        _times_two, "resource", {(0, 25), (15, 49)}, 4, 0, [],
    ),
    # controls: nothing to take a window of. A resident block shorter
    # than its rung is the rung's first size and runs exact (ISSUE 32)
    "control-one-block-short-of-its-rung": (
        "map_blocks", lambda: _resident({"x": _ints(40)}, [40]),
        _times_two, None, set(), 0, 0, [40],
    ),
    "control-numpy-column": (
        "map_blocks",
        lambda: tfs.TensorFrame.from_dict({"x": _ints(40)}, num_blocks=4),
        _times_two, None, set(), 0, 4, [],
    ),
    "control-numpy-column-map_rows": (
        "map_rows",
        lambda: tfs.TensorFrame.from_dict(
            {"x": _ints(40, width=3)}, num_blocks=4
        ),
        _rows_times_two, None, set(), 0, 4, [],
    ),
}


class TestBlockWindow:
    @pytest.mark.parametrize("case", sorted(_WINDOW_CASES))
    def test_bit_identical_to_unbucketed(self, case, monkeypatch):
        from tensorframes_tpu.testing import faults as chaos
        from tensorframes_tpu.utils import telemetry as tele

        verb, make, fetch_of, fault, slices, windows, padded, first = (
            _WINDOW_CASES[case]
        )
        df = make()
        run, fetch = getattr(tfs, verb), fetch_of(df)
        with tfs.config.override(shape_bucketing=False):
            want = np.asarray(run(fetch, df)["z"].values)
        tele.reset()
        seen, unpad = set(), sp.block_unpad

        def spy(shift, n, *outs):
            seen.add((shift, n))
            return unpad(shift, n, *outs)

        monkeypatch.setattr(sp, "block_unpad", spy)
        ex = Executor()  # its programs' ledgers have seen no size yet
        if fault:
            with chaos.inject(nth=[0], fault=fault):
                got = run(fetch, df, executor=ex)["z"].values
        else:
            got = run(fetch, df, executor=ex)["z"].values
        np.testing.assert_array_equal(np.asarray(got), want)
        assert seen == slices
        c = tele.flat_counters()
        assert c.get("shape_bucketing.window_dispatch", 0) == windows
        assert c.get("shape_bucketing.padded_dispatch", 0) == padded
        assert c.get("shape_bucketing.first_size_dispatch", 0) == len(first)
        # the same `bucket - n` a dispatch on either path, none on a
        # first size; the split's failed first attempt of block 0 (50
        # rows) counted its window too
        sizes = df.block_sizes() + ([25, 25] if fault else [])
        assert c.get("shape_bucketing.pad_rows", 0) == sum(
            sp.bucket_for(n) - n for n in sizes if n not in first
        )

    def test_block_feeds_takes_what_the_columns_show(self):
        import jax

        def cut():
            raise AssertionError("a window needs no cut")

        x = jax.device_put(_ints(100))
        feeds, bucket, shift = sp.block_feeds([x], 90, 100, cut)
        assert (bucket, shift) == (16, 6)
        np.testing.assert_array_equal(np.asarray(feeds[0]), _ints(100)[84:])
        # on its rung, shorter than its rung, on the host, or sharded
        # over devices: the caller's cut, padded by replication
        sharded = jax.device_put(
            _ints(64),
            jax.sharding.NamedSharding(
                jax.sharding.Mesh(np.array(jax.devices()[:2]), ("d",)),
                jax.sharding.PartitionSpec("d"),
            ),
        )
        for cols, lo, hi in [
            ([x], 0, 16), ([x[:10]], 0, 10), ([_ints(100)], 0, 10),
            ([sharded], 0, 10), ([x, _ints(100)], 0, 10),
        ]:
            feeds, bucket, shift = sp.block_feeds(
                cols, lo, hi, lambda: [c[lo:hi] for c in cols]
            )
            assert shift is None and bucket == 16
            assert all(f.shape[0] == 16 for f in feeds)

    def test_compiles_bounded_over_drifting_blocks(self):
        """Block sizes drift over one resident frame: the verb's program
        and the window helper each compile at most once a rung, and a
        second call on the same frame compiles nothing."""
        sizes = [3, 9, 17, 31, 64, 101, 7, 55, 5, 12, 90, 33]
        df = _resident({"x": _ints(sum(sizes))}, sizes)
        rungs = len(set(df.bucketed_block_sizes()))
        assert rungs < len(set(sizes))
        ex = Executor()
        fetch = (tfs.block(df, "x") * 2.0 + 1.0).named("y")
        w0, u0 = sp.block_window._cache_size(), sp.block_unpad._cache_size()
        with tfs.config.override(block_scheduler="off"):
            out = tfs.map_blocks(fetch, df, executor=ex)
            np.testing.assert_array_equal(
                np.asarray(out["y"].values), _ints(sum(sizes)) * 2.0 + 1.0
            )
            assert ex.jit_shape_compiles() <= rungs
            assert sp.block_window._cache_size() - w0 <= rungs
            w1, u1 = (
                sp.block_window._cache_size(), sp.block_unpad._cache_size()
            )
            # the unpad is a static slice: one a distinct (rung, shift, n)
            assert u1 - u0 <= len(set(sizes))
            n_compiles = ex.jit_shape_compiles()
            tfs.map_blocks(fetch, df, executor=ex)
            assert ex.jit_shape_compiles() == n_compiles
            assert sp.block_window._cache_size() == w1
            assert sp.block_unpad._cache_size() == u1


# ---------------------------------------------------------------------------
# the block group (ISSUE 34, 36): a run of equal blocks of resident columns
# is one dispatch, one pass of the program over the run's rows
# ---------------------------------------------------------------------------


def _two_fetches(df):
    x, y = tfs.block(df, "x"), tfs.block(df, "y")
    return [(x * 2.0 + y).named("z"), (x - y).named("w")]


def _group_counters():
    from tensorframes_tpu.utils import telemetry as tele

    c = tele.flat_counters()
    return {
        k: int(c[f"shape_bucketing.{k}"]) if f"shape_bucketing.{k}" in c else None
        for k in ("group_dispatch", "grouped_blocks", "window_dispatch",
                  "padded_dispatch", "first_size_dispatch", "pad_rows")
    }


# case -> (verb, frame, fetches, scheduler, groups, blocks they cover,
# windows, padded dispatches, first-size dispatches, pad rows); rungs 8,
# 16, 32, ... The scheduler is a `config.block_scheduler` mode, or
# "devices": an explicit `devices=` of four (the user's placement, block
# for block: no home plan, ISSUE 39).
_GROUP_CASES = {
    "200-equal-blocks": (
        "map_blocks", lambda: _resident({"x": _ints(2000)}, [10] * 200),
        _times_two, "off", 1, 200, 0, 0, 0, 0,
    ),
    # blocks on their rung are a run like any other
    "equal-blocks-on-their-rung": (
        "map_blocks", lambda: _resident({"x": _ints(64)}, [16] * 4),
        _times_two, "off", 1, 4, 0, 0, 0, 0,
    ),
    # the remainder is a window of the same column
    "equal-blocks-and-a-remainder": (
        "map_blocks", lambda: _resident({"x": _ints(57)}, [10] * 5 + [7]),
        _times_two, "off", 1, 5, 1, 0, 0, 1,
    ),
    # an empty block holds no rows and ends no run: 10 10 10 | 7 | 4 4
    "empty-blocks-inside-and-between-runs": (
        "map_blocks",
        lambda: _resident(
            {"x": _ints(45)}, [0, 10, 0, 10, 10, 0, 7, 0, 4, 0, 0, 4, 0]
        ),
        _times_two, "off", 2, 5, 1, 0, 0, 1,
    ),
    # a run, a lone block (off its rung: a window), the first size again
    "runs-of-one-size-apart": (
        "map_blocks",
        lambda: _resident({"x": _ints(53)}, [10, 10, 13, 10, 10]),
        _times_two, "off", 2, 4, 1, 0, 0, 3,
    ),
    # a strict part of its column: rows before it (a window of 7 in a
    # rung of 8) and after it (5 in 8)
    "run-inside-its-column": (
        "map_blocks",
        lambda: _resident({"x": _ints(42)}, [7, 10, 10, 10, 5]),
        _times_two, "off", 1, 3, 2, 0, 0, 4,
    ),
    "two-feed-columns-two-fetches": (
        "map_blocks",
        lambda: _resident(
            {"x": _ints(80), "y": _ints(80, mod=7)}, [20] * 4
        ),
        _two_fetches, "off", 1, 4, 0, 0, 0, 0,
    ),
    "map_rows-dense-route": (
        "map_rows", lambda: _resident({"x": _ints(60, width=3)}, [20] * 3),
        _rows_times_two, "off", 1, 3, 0, 0, 0, 0,
    ),
    # a schedule the scheduler made itself keeps a row-local map on the
    # device that holds its column (the home plan, ISSUE 39): the group
    "home-plan-auto": (
        "map_blocks", lambda: _resident({"x": _ints(100)}, [10] * 10),
        _times_two, "auto", 1, 10, 0, 0, 0, 0,
    ),
    "home-plan-on-and-a-remainder": (
        "map_blocks", lambda: _resident({"x": _ints(57)}, [10] * 5 + [7]),
        _times_two, "on", 1, 5, 1, 0, 0, 1,
    ),
    # ... but `map_rows`' program may be worth moving: spread as before
    "control-map_rows-under-a-scheduler": (
        "map_rows", lambda: _resident({"x": _ints(60, width=3)}, [20] * 3),
        _rows_times_two, "auto", 0, 0, 3, 0, 0, 36,
    ),
    # controls: no run of two, no resident column, more than one device
    "control-one-block": (
        "map_blocks", lambda: _resident({"x": _ints(40)}, [40]),
        _times_two, "off", 0, 0, 0, 0, 1, 0,
    ),
    "control-no-neighbour-shares-a-size": (
        "map_blocks", lambda: _resident({"x": _ints(38)}, [10, 9, 10, 9]),
        _times_two, "off", 0, 0, 4, 0, 0, 26,
    ),
    "control-numpy-column": (
        "map_blocks",
        lambda: tfs.TensorFrame.from_dict({"x": _ints(40)}, num_blocks=4),
        _times_two, "off", 0, 0, 0, 4, 0, 24,
    ),
    "control-numpy-column-beside-a-resident-one": (
        "map_blocks",
        lambda: tfs.TensorFrame(
            [_resident({"x": _ints(40)}, [40])["x"],
             tfs.Column("y", _ints(40, mod=7))],
            [0, 10, 20, 30, 40],
        ),
        _two_fetches, "off", 0, 0, 0, 4, 0, 24,
    ),
    "control-scheduler-over-devices": (
        "map_blocks", lambda: _resident({"x": _ints(100)}, [10] * 10),
        _times_two, "devices", 0, 0, 10, 0, 0, 60,
    ),
}


def _spy_group_compiles(monkeypatch):
    """The rows of every run `shape_policy._compile_group` is asked to
    compile from here on, in order."""
    made, compile_group = [], sp._compile_group

    def spy(book, rows, avals, device):
        made.append(rows)
        return compile_group(book, rows, avals, device)

    monkeypatch.setattr(sp, "_compile_group", spy)
    return made


def _run_columns(verb, fetch, df, **kw):
    out = getattr(tfs, verb)(fetch, df, **kw)
    names = ["z", "w"] if isinstance(fetch, list) else ["z"]
    return {n: np.asarray(out[n].values) for n in names}, out


class TestBlockGroup:
    @pytest.mark.parametrize("case", sorted(_GROUP_CASES))
    def test_bit_identical_to_the_block_loop(self, case):
        from tensorframes_tpu.utils import telemetry as tele

        (verb, make, fetch_of, scheduler, groups, covered, windows, padded,
         first, pad_rows) = _GROUP_CASES[case]
        df = make()
        fetch = fetch_of(df)
        # the block loop, unbucketed, and the block loop on the ladder
        # (explicit devices keep it): both ways the program sees each block
        import jax

        four = jax.local_devices()[:4]
        with tfs.config.override(shape_bucketing=False):
            want, _ = _run_columns(verb, fetch, df)
        laddered, _ = _run_columns(
            verb, fetch, df, executor=Executor(), devices=four
        )
        tele.reset()
        if scheduler == "devices":
            got, out = _run_columns(
                verb, fetch, df, executor=Executor(), devices=four
            )
        else:
            with tfs.config.override(block_scheduler=scheduler):
                got, out = _run_columns(verb, fetch, df, executor=Executor())
        for name in want:
            np.testing.assert_array_equal(got[name], want[name])
            np.testing.assert_array_equal(got[name], laddered[name])
        assert out.offsets == df.offsets
        c = _group_counters()
        assert (c["group_dispatch"] or 0, c["grouped_blocks"] or 0) == (
            groups, covered
        )
        assert (c["window_dispatch"] or 0, c["padded_dispatch"] or 0) == (
            windows, padded
        )
        assert (c["first_size_dispatch"] or 0) == first
        # a group counts its pad rows too, as none: a frame that is one
        # run reads 0, not nothing (`pad_rows_pct`)
        assert c["pad_rows"] == pad_rows

    def test_one_dispatch_one_span_one_part(self):
        """A frame that is one run: one dispatch span with the run's
        blocks and rows under the stage span, no pad, unpad, cut or
        concat, and `pad_rows` counted as 0."""
        from tensorframes_tpu.utils import telemetry as tele

        df = _resident({"x": _ints(2000)}, [10] * 200)
        with tfs.config.override(block_scheduler="off"):
            tfs.map_blocks(_times_two(df), df, executor=Executor())
        ss = tele.spans()
        (block,) = [s for s in ss if s.name == "map_blocks.block"]
        (stage,) = [s for s in ss if s.name == "map_blocks.blocks"]
        assert block.kind == "dispatch" and block.parent_id == stage.span_id
        assert (block.attrs["blocks"], block.attrs["rows"]) == (200, 2000)
        assert block.attrs["bucket"] == 2000 and block.attrs["block"] == 0
        assert not {s.name for s in ss} & {
            "shape.pad", "shape.unpad", "frame.cut", "frame.concat"
        }
        assert tele.flat_counters()["shape_bucketing.pad_rows"] == 0
        bk = tfs.diagnostics(format="json")["bucketing"]
        assert (bk["group_dispatches"], bk["grouped_blocks"]) == (1, 200)
        (line,) = [l for l in tfs.diagnostics().splitlines()
                   if l.startswith("bucketing:")]
        assert "1 group dispatch(es) over 200 equal block(s)" in line

    def test_group_keeps_the_module_name(self, monkeypatch):
        """The group's program is the verb's program over the run's
        rows: in a device trace it is still `jit_fn`, so the benchmark's
        `program_roofline` reads it and `copy_device_pct` does not, and
        it holds no loop over the blocks and no carried output."""
        import re

        made, compile_exact = [], sp._compile_exact

        def spy(*args):
            made.append(compile_exact(*args))
            return made[-1]

        monkeypatch.setattr(sp, "_compile_exact", spy)
        with tfs.config.override(block_scheduler="off"):
            for verb, fetch_of, width in (
                ("map_blocks", _times_two, None),
                ("map_rows", _rows_times_two, 3),
            ):
                df = _resident({"x": _ints(40, width=width)}, [10] * 4)
                getattr(tfs, verb)(fetch_of(df), df, executor=Executor())
        assert len(made) == 2
        for compiled in made:
            text = compiled.as_text()
            assert re.search(r"HloModule (\w+)", text).group(1) == "jit_fn"
            assert " while(" not in text
            assert "dynamic-update-slice" not in text

    def test_compiles_bounded_over_drifting_runs(self, monkeypatch):
        """Frames whose equal blocks keep changing in size and count: a
        program holds no more group executables than its ledger has
        lines (one a run's rows, columns and device), compiles at first
        sight only while the ledger has room, and after that only a run
        that came back."""
        made = _spy_group_compiles(monkeypatch)
        ex = Executor()

        def call(n, k, tail=()):
            df = _resident({"x": _ints(n * k + sum(tail))}, [n] * k + list(tail))
            with tfs.config.override(shape_bucketing=False):
                want = np.asarray(tfs.map_blocks(_times_two(df), df)["z"].values)
            got = tfs.map_blocks(_times_two(df), df, executor=ex)["z"].values
            np.testing.assert_array_equal(np.asarray(got), want)

        with tfs.config.override(
            block_scheduler="off", executor_cache_entries=3
        ):
            for n, k in [(10, 4), (11, 4), (10, 5)]:
                call(n, k)
            assert made == [40, 44, 50]
            call(10, 4)  # held: nothing compiles
            call(10, 4, tail=(3,))  # the same run in a longer column
            assert made[3:] == []  # ... a fourth signature: the ledger is full
            (book,) = [e.ledger for e in ex.programs()]
            assert len(book.groups) == 3
            # drift: sizes and counts that never repeat compile nothing more
            shapes0 = ex.jit_shape_compiles()
            for n, k in [(12, 3), (13, 6), (14, 2), (15, 7), (9, 9), (17, 3)]:
                call(n, k)
            assert made[3:] == [] and len(book.groups) == 3
            # ... and ran block by block on the ladder (rungs 16 and 32)
            assert ex.jit_shape_compiles() - shapes0 <= 2
            c = _group_counters()
            assert c["group_dispatch"] == 4 and c["window_dispatch"] == (
                (4 + 1) + 3 + 6 + 2 + 7 + 9 + 3  # the tail's block too
            )
            # a run that comes back while its line is held earns its program
            call(17, 3)
            assert made[3:] == [51]
            call(17, 3)
            assert made[3:] == [51] and len(book.groups) == 3
            assert _group_counters()["group_dispatch"] == 6
        # the lines go with the cache entry
        ex.clear()
        assert not ex.programs()

    def test_splits_of_the_same_rows_share_one_executable(self, monkeypatch):
        """What a run executes depends on its rows, not on how they are
        cut: 4 x 10 and 5 x 8 of one column are one ledger line and one
        compile, and each still counts its own blocks."""
        made = _spy_group_compiles(monkeypatch)
        ex = Executor()
        with tfs.config.override(block_scheduler="off"):
            for sizes in ([10] * 4, [8] * 5):
                df = _resident({"x": _ints(40)}, sizes)
                with tfs.config.override(shape_bucketing=False):
                    want, _ = _run_columns("map_blocks", _times_two(df), df)
                got, out = _run_columns(
                    "map_blocks", _times_two(df), df, executor=ex
                )
                np.testing.assert_array_equal(got["z"], want["z"])
                assert out.offsets == df.offsets
        assert made == [40]
        (book,) = [e.ledger for e in ex.programs()]
        assert len(book.groups) == 1
        c = _group_counters()
        assert (c["group_dispatch"], c["grouped_blocks"]) == (2, 9)

    @pytest.mark.parametrize("scheduler", ["off", "auto"])
    @pytest.mark.parametrize("fault", ["resource", "transient"])
    def test_faults_keep_their_meaning(self, fault, scheduler):
        """With no schedule a transient fault retries the group; under a
        home plan (ISSUE 39) it hands the run to the block loop, which
        owns retry and failover, as a group that runs out of memory does
        either way (the loop may split). The schedule's books count
        each block once, on the column's device."""
        from tensorframes_tpu.runtime.scheduler import device_label
        from tensorframes_tpu.testing import faults as chaos
        from tensorframes_tpu.utils import telemetry as tele

        df = _resident({"x": _ints(50)}, [10] * 5)
        with tfs.config.override(shape_bucketing=False):
            want = np.asarray(tfs.map_blocks(_times_two(df), df)["z"].values)
        tele.reset()
        with tfs.config.override(block_scheduler=scheduler):
            with chaos.inject(nth=[0], fault=fault) as plan:
                got = tfs.map_blocks(_times_two(df), df, executor=Executor())
        np.testing.assert_array_equal(np.asarray(got["z"].values), want)
        assert plan.faulted_ordinals == [0]
        c = _group_counters()
        stats = executor_stats()["faults"]
        if fault == "transient" and scheduler == "off":
            assert plan.dispatches == 2  # the group, and the group again
            assert (c["group_dispatch"], c["window_dispatch"]) == (1, None)
            assert not stats["forensics"]
        else:
            assert plan.dispatches == 1 + 5  # the group, then its blocks
            assert (c["group_dispatch"], c["window_dispatch"]) == (1, 5)
        if fault == "resource":
            (snap,) = stats["forensics"]
            assert snap["decision"] == "split:5 blocks of 10 rows, one by one"
            assert (snap["rows"], snap["depth"]) == (50, 0)
            assert stats["splits"] == 1
        flat = tele.flat_counters()
        if scheduler == "off":
            assert not [k for k in flat if k.startswith("scheduler.")]
        else:
            (home,) = df["x"].values.devices()
            lab = device_label(home)
            assert flat["scheduler.home_plans"] == 1
            assert flat["scheduler.home_blocks"] == 5
            assert flat[f"scheduler.dispatches{{device={lab}}}"] == 5
            rows = {k: v for k, v in flat.items()
                    if k.startswith("scheduler.rows{")}
            assert rows.pop(f"scheduler.rows{{device={lab}}}") == 50
            assert rows and not any(rows.values())
            assert got["z"].values.devices() == {home}

    def test_numerics_and_row_checks_see_the_group(self):
        """`check_numerics` names the run's blocks, and the outputs a
        group gives are held to the run's rows."""
        df = _resident(
            {"x": np.array([1.0] * 10 + [0.0] * 10, np.float32)}, [5] * 4
        )
        fetch = (1.0 / tfs.block(df, "x")).named("z")
        with tfs.config.override(block_scheduler="off", check_numerics=True):
            with pytest.raises(Exception, match=r"map_blocks blocks \[0:4\)"):
                tfs.map_blocks(fetch, df, executor=Executor())


# ---------------------------------------------------------------------------
# promotion (ISSUE 29): a repeated replicated pad buys its exact shape,
# from its rung's second size on (ISSUE 32: the first runs exact at once)
# ---------------------------------------------------------------------------

# bytes one padded call of `_promo_frame()` moves beyond an exact one:
# the pad copy, the pad rows through the program, the slice. As the
# bandwidth it makes a call's rent 1.0 s, so a price reads in calls.
_PROMO_ROWS = 1000
_PROMO_CALL_BYTES = (1000 + 1024) * 4 + 24 * 8 + 2 * 1000 * 4


class _Promo:
    """One executor under an injected bandwidth and price: `call` runs
    the verb, holds the output to unbucketed execution bit for bit, and
    waits for a promotion it may have started. The price: the
    executor's clock steps by ``price`` seconds a reading, so that is
    what every compile it times has taken."""

    def __init__(self, monkeypatch, price, bandwidth=_PROMO_CALL_BYTES):
        import types

        from tensorframes_tpu.runtime import costmodel, executor

        self.monkeypatch = monkeypatch
        self.ex = Executor()
        self.price, self._now = float(price), 0.0
        monkeypatch.setattr(
            executor, "time", types.SimpleNamespace(perf_counter=self._tick)
        )
        if bandwidth is not None:
            monkeypatch.setitem(
                costmodel.DEVICE_PEAKS, "cpu", {"hbm_bytes_s": bandwidth}
            )

    def _tick(self):
        self._now += self.price
        return self._now

    def frame(self, rows=_PROMO_ROWS, sizes=None, **more):
        return _resident({"x": _ints(rows), **more}, sizes or [rows])

    def widen(self, verb="map_blocks", fetch_of=_times_two, **more):
        """Show rung 1024 of the verb's program a first size, 999 rows,
        which runs exact (ISSUE 32): `frame()`'s 1,000 rows are then a
        second size there, the one that pads and pays rent."""
        self.call(_resident({"x": _ints(999), **more}, [999]), verb, fetch_of)
        c = self.counters()
        assert (c["first_size_dispatch"], c["padded_dispatch"]) == (1, 0)

    def call(self, df, verb="map_blocks", fetch_of=_times_two, wait=True):
        run, fetch = getattr(tfs, verb), fetch_of(df)
        with tfs.config.override(shape_bucketing=False):
            want = np.asarray(run(fetch, df)["z"].values)
        got = run(fetch, df, executor=self.ex)["z"].values
        np.testing.assert_array_equal(np.asarray(got), want)
        if wait:
            assert sp.drain(self.ex, timeout=60)

    def counters(self):
        from tensorframes_tpu.utils import telemetry as tele

        c = tele.flat_counters()
        return {
            k: int(c.get("shape_bucketing." + k, 0))
            for k in ("padded_dispatch", "window_dispatch", "pad_rows",
                      "first_size_dispatch", "rungs_widened",
                      "promoted_dispatch", "promotions", "promotion_failed",
                      "promotion_unpriced")
        }

    def lines(self):
        return [
            line for entry in self.ex.programs()
            for line in entry.ledger.shapes.values()
        ]


def _promo_repeated(p):
    """Padded until the rent (1.0 a call) reaches the price (3.0), then
    the exact executable: no pad row, no new jit specialization."""
    p.widen()
    df = p.frame()
    for _ in range(3):
        p.call(df)
    c = p.counters()
    assert (c["padded_dispatch"], c["promoted_dispatch"]) == (3, 0)
    assert c["promotions"] == 1 and c["pad_rows"] == 3 * 24
    for _ in range(3):
        p.call(df)
    c = p.counters()
    assert (c["padded_dispatch"], c["promoted_dispatch"]) == (3, 3)
    assert c["promotions"] == 1 and c["pad_rows"] == 3 * 24
    # the rung and its first size; the bought one is apart
    assert p.ex.jit_shape_compiles() == 2
    assert (c["first_size_dispatch"], c["rungs_widened"]) == (1, 1)
    assert [line.rent for line in p.lines()] == [3.0]


def _promo_map_rows(p):
    # three times the row bytes: a call's rent is the price
    p.widen("map_rows", _rows_times_two, x=_ints(999, width=3))
    df = _resident({"x": _ints(_PROMO_ROWS, width=3)}, [_PROMO_ROWS])
    p.call(df, "map_rows", _rows_times_two)
    p.call(df, "map_rows", _rows_times_two)
    c = p.counters()
    assert (c["padded_dispatch"], c["promoted_dispatch"]) == (1, 1)


def _promo_two_columns(p):
    # two feeds, one output: (2024 * 8 + 24 * 12 + 2000 * 4) bytes a call
    p.widen(fetch_of=_two_columns, y=_ints(999, mod=7))
    df = p.frame(y=_ints(_PROMO_ROWS, mod=7))
    rent = (2024 * 8 + 24 * 12 + 2000 * 4) / _PROMO_CALL_BYTES
    assert 1.5 < rent < 3.0
    p.call(df, fetch_of=_two_columns)
    assert p.counters()["promotions"] == 0
    assert p.lines()[0].rent == pytest.approx(rent)
    p.call(df, fetch_of=_two_columns)
    p.call(df, fetch_of=_two_columns)
    c = p.counters()
    assert (c["padded_dispatch"], c["promoted_dispatch"]) == (2, 1)


def _promo_sizes_never_repeat(p):
    """Drift: the rung's first size runs exact, every other size seen
    once pays one call's rent and buys nothing; the compiles stay on
    the ladder, and the first size's one beside it."""
    for rows in range(990, 1010):
        p.call(p.frame(rows))
    c = p.counters()
    assert c["padded_dispatch"] == 19 and c["promotions"] == 0
    assert (c["first_size_dispatch"], c["promoted_dispatch"]) == (1, 0)
    assert p.ex.jit_shape_compiles() == 2
    assert len(p.lines()) == 19
    assert all(line.thread is None for line in p.lines())


def _promo_rent_under_price(p):
    """A small block that repeats: its pad costs far less than a
    compile, so it stays on its rung."""
    p.price = 1e6
    p.widen()
    df = p.frame()
    for _ in range(10):
        p.call(df)
    c = p.counters()
    assert (c["padded_dispatch"], c["promotions"]) == (10, 0)
    assert p.lines()[0].rent == pytest.approx(10.0)


def _promo_multi_block_keeps_window(p):
    p.price = 0.0
    df = p.frame(95, sizes=[10, 9] * 5)
    for _ in range(3):
        p.call(df)
    c = p.counters()
    assert c["window_dispatch"] == 30 and c["padded_dispatch"] == 0
    assert c["promoted_dispatch"] == 0 and not p.lines()


def _promo_numpy_column_keeps_pad(p):
    p.price = 0.0
    df = tfs.TensorFrame.from_dict({"x": _ints(_PROMO_ROWS)})
    for _ in range(3):
        p.call(df)
    c = p.counters()
    assert c["padded_dispatch"] == 3 and not p.lines()


def _promo_compile_fails(p):
    def refuse(jitted, avals, device):
        raise RuntimeError("no such shape today")

    p.monkeypatch.setattr(sp, "_compile_exact", refuse)
    p.widen()
    df = p.frame()
    for _ in range(5):
        p.call(df)
    c = p.counters()
    assert c["promotion_failed"] == 1 and c["promotions"] == 0
    assert (c["padded_dispatch"], c["promoted_dispatch"]) == (5, 0)


def _promo_unknown_device_kind(p):
    p.price = 0.0
    p.widen()  # needs no bandwidth: the first size has no price
    df = p.frame()
    for _ in range(4):
        p.call(df)
    c = p.counters()
    assert c["promotion_unpriced"] == 1 and c["promotions"] == 0
    assert (c["padded_dispatch"], c["promoted_dispatch"]) == (4, 0)


def _promo_unpriced_program(p):
    """No compile of the program was timed: nothing to weigh a rent
    against, so nothing is bought."""
    p.price = 1e6
    p.widen()
    df = p.frame()
    p.call(df)
    for entry in p.ex.programs():
        entry.ledger.compile_seconds = None
    for _ in range(4):
        p.call(df)
    c = p.counters()
    assert (c["padded_dispatch"], c["promotions"]) == (5, 0)


def _promo_pending_compile_never_blocks(p):
    """The calling thread never compiles for a promotion: while the
    compile is held, calls return on the pad."""
    import threading

    release, compile_exact = threading.Event(), sp._compile_exact
    callers = set()

    def held(jitted, avals, device):
        callers.add(threading.get_ident())
        assert release.wait(60)
        return compile_exact(jitted, avals, device)

    p.monkeypatch.setattr(sp, "_compile_exact", held)
    p.widen()
    df = p.frame()
    try:
        for _ in range(6):
            p.call(df, wait=False)
        c = p.counters()
        assert (c["padded_dispatch"], c["promoted_dispatch"]) == (6, 0)
        assert not sp.drain(p.ex, timeout=0.01)
    finally:
        release.set()
    assert sp.drain(p.ex, timeout=60)
    assert callers and threading.get_ident() not in callers
    p.call(df)
    c = p.counters()
    assert (c["padded_dispatch"], c["promoted_dispatch"]) == (6, 1)
    assert c["promotions"] == 1


def _promo_dropped_with_the_cache_entry(p):
    import gc
    import weakref

    p.price = 1.0
    p.widen()
    df = p.frame()
    p.call(df)
    p.call(df)
    assert p.counters()["promoted_dispatch"] == 1
    bought = weakref.ref(p.lines()[0].exact)
    p.ex.clear()
    gc.collect()  # the last call's `_dispatch_rows` cycle held what it called
    assert bought() is None and not p.ex.programs()
    # a new entry: its ledger starts empty, so the size it meets first
    # is its rung's first now, and no line is kept for it
    p.call(df)
    p.call(df)
    c = p.counters()
    assert (c["first_size_dispatch"], c["padded_dispatch"]) == (3, 1)
    assert c["promoted_dispatch"] == 1 and not p.lines()
    # evicted by another program under a one-entry cache: the same
    with tfs.config.override(executor_cache_entries=1):
        p.call(df, fetch_of=lambda d: (tfs.block(d, "x") + 1.0).named("z"))
        assert len(p.ex.programs()) == 1
        p.call(p.frame(999))
        p.call(df)
        p.call(df)
    c = p.counters()
    assert (c["first_size_dispatch"], c["padded_dispatch"]) == (5, 2)
    assert (c["promoted_dispatch"], c["promotions"]) == (2, 2)


def _promo_ledger_bounded(p):
    p.price = 1e6
    with tfs.config.override(executor_cache_entries=2):
        for rows in (1000, 1001, 1002, 1001, 1003):
            p.call(p.frame(rows))
    assert sorted(line.rows for line in p.lines()) == [1001, 1003]


def _promo_oom_split_recurses(p):
    """A promoted dispatch that runs out of memory splits like any
    other: its halves are windows of the same column."""
    from tensorframes_tpu.testing import faults as chaos

    p.price = 1.0
    p.widen()
    df = p.frame()
    p.call(df)
    p.call(df)
    assert p.counters()["promoted_dispatch"] == 1
    with chaos.inject(nth=[1], fault="resource"):  # [0]: the reference
        p.call(df)
    c = p.counters()
    assert c["promoted_dispatch"] == 2 and c["window_dispatch"] == 2
    assert c["pad_rows"] == 24 + 2 * 12


def _promo_failover_leaves_the_device(p):
    """A transient fault re-places the block: the executable bought for
    the first device hands the feeds to the program itself."""
    from tensorframes_tpu.testing import faults as chaos

    p.price = 1.0
    p.widen()
    df = p.frame()
    p.call(df)
    p.call(df)
    assert p.ex.jit_shape_compiles() == 2  # the first size, the rung
    with chaos.inject(nth=[1], fault="transient"):
        p.call(df)
    assert p.counters()["promoted_dispatch"] == 2
    assert p.ex.jit_shape_compiles() == 3  # the exact shape, elsewhere


_PROMOTION_CASES = {
    "repeated-one-block": _promo_repeated,
    "map_rows-2d-column": _promo_map_rows,
    "two-feed-columns": _promo_two_columns,
    "sizes-never-repeat": _promo_sizes_never_repeat,
    "rent-under-price": _promo_rent_under_price,
    "multi-block-keeps-window": _promo_multi_block_keeps_window,
    "numpy-column-keeps-pad": _promo_numpy_column_keeps_pad,
    "compile-fails": _promo_compile_fails,
    "unpriced-program": _promo_unpriced_program,
    "pending-compile-never-blocks": _promo_pending_compile_never_blocks,
    "dropped-with-the-cache-entry": _promo_dropped_with_the_cache_entry,
    "ledger-bounded": _promo_ledger_bounded,
    "oom-split-recurses": _promo_oom_split_recurses,
    "failover-leaves-the-device": _promo_failover_leaves_the_device,
}


class TestPromotion:
    @pytest.mark.parametrize("case", sorted(_PROMOTION_CASES))
    def test_rent_buys_the_exact_shape(self, case, monkeypatch):
        _PROMOTION_CASES[case](_Promo(monkeypatch, price=3.0))

    def test_unknown_device_kind_is_never_promoted(self, monkeypatch):
        _promo_unknown_device_kind(
            _Promo(monkeypatch, price=3.0, bandwidth=None)
        )


# ---------------------------------------------------------------------------
# the first size of a rung (ISSUE 32): exact at first sight, the pad
# from the rung's second size on
# ---------------------------------------------------------------------------


def _first_runs_exact(p):
    """A resident one-block frame off its rung never takes the pad: one
    compile, at its exact shape, on the calling thread; nothing priced."""
    from tensorframes_tpu.utils import telemetry as tele

    df = p.frame()
    for _ in range(3):
        p.call(df)
    c = p.counters()
    assert (c["first_size_dispatch"], c["padded_dispatch"]) == (3, 0)
    assert c["rungs_widened"] == c["promotion_unpriced"] == 0
    # counted, as none: not a program that does not count its pad rows
    assert tele.flat_counters()["shape_bucketing.pad_rows"] == 0
    assert p.ex.jit_shape_compiles() == 1 and not p.lines()
    # the bucketed calls' (the unbucketed reference's carry no bucket)
    blocks = [s for s in tele.spans() if s.name == "map_blocks.block"
              and s.attrs.get("bucket") is not None]
    assert len(blocks) == 3
    assert all(s.attrs["bucket"] == s.attrs["rows"] == 1000 for s in blocks)
    assert not {s.name for s in tele.spans()} & {
        "shape.pad", "shape.unpad", "shape.promote"
    }


def _first_second_size_widens(p):
    """The second size on a rung is what the ladder is for: it pads and
    compiles the rung's program, once; a third compiles nothing; the
    first keeps its exact executable."""
    p.call(p.frame(1000))
    assert p.ex.jit_shape_compiles() == 1
    p.call(p.frame(1001))
    c = p.counters()
    assert (c["padded_dispatch"], c["rungs_widened"]) == (1, 1)
    assert c["pad_rows"] == 23 and p.ex.jit_shape_compiles() == 2
    p.call(p.frame(1002))
    p.call(p.frame(1001))
    p.call(p.frame(1000))
    c = p.counters()
    assert (c["first_size_dispatch"], c["padded_dispatch"]) == (2, 3)
    assert c["rungs_widened"] == 1 and c["pad_rows"] == 23 + 22 + 23
    assert p.ex.jit_shape_compiles() == 2
    assert sorted(line.rows for line in p.lines()) == [1001, 1002]


def _first_drift_compiles_twice_a_rung(p):
    """The bound: over one-block frames whose sizes drift, a program
    compiles at most one shape more per rung than the ladder alone."""
    rng = np.random.RandomState(7)
    sizes = [int(n) for n in rng.randint(9, 3000, size=40)] + [64, 2048]
    rungs = {sp.bucket_for(n) for n in sizes}
    assert len(rungs) < len(set(sizes)) / 3
    for n in sizes:
        p.call(p.frame(n))
    c = p.counters()
    assert p.ex.jit_shape_compiles() <= 2 * len(rungs)
    assert c["rungs_widened"] <= len(rungs)
    assert c["first_size_dispatch"] + c["padded_dispatch"] == len(
        [n for n in sizes if sp.bucket_for(n) != n]
    )
    n_compiles = p.ex.jit_shape_compiles()
    for n in sizes:
        p.call(p.frame(n))
    assert p.ex.jit_shape_compiles() == n_compiles


def _first_keyed_as_the_ledger(p):
    """Trailing shapes, dtypes and the device the scheduler names key a
    rung's first size as they key a ledger line (`_line`)."""
    import jax

    d0, d1 = jax.devices()[:2]
    graph, fetches = dsl.build(_two_columns(p.frame(8, y=_ints(8))))
    program = p.ex.callable_for(graph, fetches, ["x", "y"])
    book = program.ledger
    f32 = np.dtype(np.float32)

    def dispatch(n, device=None, width=None):
        cols = [jax.device_put(_ints(n), d0),
                jax.device_put(_ints(n, width=width), d0)]
        return sp.block_dispatch(program, cols, 0, n, lambda: cols, device)

    assert dispatch(1000).bucket == 1000  # first on (1024, two vectors, d0)
    assert dispatch(1000, d1).bucket == 1000  # another device: its own first
    assert dispatch(1001, d1).bucket == 1024  # a second size there
    assert dispatch(1001, width=3).bucket == 1001  # other trailing shapes
    assert dispatch(1001).bucket == 1024
    assert dispatch(1000, d0).bucket == 1000  # d0 named is d0 resident
    assert set(book.rungs) == {
        (1024, (((), f32), ((), f32)), d0),
        (1024, (((), f32), ((), f32)), d1),
        (1024, (((), f32), ((3,), f32)), d0),
    }
    assert {sig[1] for sig in book.shapes} == {d0, d1}
    c = p.counters()
    assert (c["first_size_dispatch"], c["padded_dispatch"]) == (4, 2)
    assert c["rungs_widened"] == 2


_FIRST_SIZE_CASES = {
    "runs-exact-from-the-first-call": _first_runs_exact,
    "second-size-widens-the-rung": _first_second_size_widens,
    "drift-compiles-twice-a-rung": _first_drift_compiles_twice_a_rung,
    "keyed-as-the-ledger": _first_keyed_as_the_ledger,
}


class TestFirstSize:
    @pytest.mark.parametrize("case", sorted(_FIRST_SIZE_CASES))
    def test_first_size_of_a_rung_runs_exact(self, case, monkeypatch):
        # no bandwidth known, no price that rent could reach: the rule
        # needs neither
        _FIRST_SIZE_CASES[case](_Promo(monkeypatch, price=1e9, bandwidth=None))


class TestBucketedReduce:
    @pytest.mark.parametrize("op", ["sum", "min", "max", "mean"])
    def test_reduce_matches_unbucketed(self, op):
        df = _uneven([3, 9, 17, 31, 64, 101, 7, 55])
        r_on = tfs.reduce_blocks(_reduce(df, op), df, executor=Executor())
        with tfs.config.override(shape_bucketing=False):
            r_off = tfs.reduce_blocks(_reduce(df, op), df, executor=Executor())
        # integer-valued float32 data: exact under any accumulation order
        assert np.asarray(r_on) == np.asarray(r_off)

    def test_reduce_int_dtypes_exact(self):
        sizes = [5, 12, 33]
        n = sum(sizes)
        df = tfs.TensorFrame(
            [
                tfs.TensorFrame.from_dict(
                    {"x": (np.arange(n) % 19).astype(np.int32)}
                )["x"]
            ],
            list(np.cumsum([0] + sizes)),
        )
        for op in ("sum", "min", "max"):
            r = tfs.reduce_blocks(_reduce(df, op), df, executor=Executor())
            with tfs.config.override(shape_bucketing=False):
                r0 = tfs.reduce_blocks(_reduce(df, op), df, executor=Executor())
            assert np.asarray(r) == np.asarray(r0)

    def test_transform_then_reduce_masks_at_root(self):
        # Sum(x^2 + 1): each pad row (a replica of the last real row)
        # would contribute last^2 + 1 to the sum unless the mask applies
        # at the transform OUTPUT — masking the input to 0 would still
        # leak +1 per pad row
        # single block (no combine: reduce_blocks re-applies the graph to
        # partials by contract, which would square them again): 5 rows
        # pad to the 8-rung — an input-level mask would leak 3 * 1.0
        df = _uneven([5])
        ph = tfs.block(df, "x", tf_name="x_input")
        fetch = dsl.reduce_sum(dsl.square(ph) + 1.0, axes=[0]).named("x")
        r = tfs.reduce_blocks(fetch, df, executor=Executor())
        want = float((df["x"].values.astype(np.float64) ** 2 + 1.0).sum())
        assert float(np.asarray(r)) == want
        # multi-block: bucketed and unbucketed agree through the combine
        df2 = _uneven([5, 13])
        r2 = tfs.reduce_blocks(fetch, df2, executor=Executor())
        with tfs.config.override(shape_bucketing=False):
            r0 = tfs.reduce_blocks(fetch, df2, executor=Executor())
        assert np.asarray(r2) == np.asarray(r0)

    def test_reduce_compile_count_bounded(self):
        sizes = list(range(1, 65))  # 64 distinct block sizes
        df = _uneven(sizes)
        ex = Executor()
        # single-device bound (scheduler-off; see TestBucketedMap note)
        with tfs.config.override(block_scheduler="off"):
            tfs.reduce_blocks(_reduce(df, "sum"), df, executor=ex)
        rungs = len(set(b for b in df.bucketed_block_sizes() if b))
        # the per-block program compiles one shape per rung; the combine
        # adds one more program/shape
        assert ex.jit_shape_compiles() <= rungs + 1
        assert rungs <= math.ceil(math.log2(max(sizes))) + 1

    def test_multi_fetch_ordering_preserved(self):
        # x/n fetches sort differently as feeds (n_input, x_input) —
        # the masked program must keep fetch->result alignment
        df = _uneven([5, 9])
        ncol = tfs.TensorFrame.from_dict(
            {"n": np.ones(df.nrows, np.float32)}
        )["n"]
        df2 = tfs.TensorFrame([df["x"], ncol], df.offsets)
        fx = _reduce(df2, "sum", "x")
        fn_ = _reduce(df2, "sum", "n")
        out = tfs.reduce_blocks([fx, fn_], df2, executor=Executor())
        assert float(np.asarray(out["x"])) == float(df2["x"].values.sum())
        assert float(np.asarray(out["n"])) == float(df2.nrows)

    def test_unclassifiable_reduce_unbucketed(self):
        # integer Mean truncates per block (TF semantics), so partials
        # cannot recombine exactly — the classifier refuses it and the
        # verb keeps the exact unbucketed program
        df = tfs.TensorFrame.from_dict(
            {"x": np.array([1, 2, 3, 4, 11], np.int32)}
        )
        ph = tfs.block(df, "x", tf_name="x_input")
        fetch = dsl.reduce_mean(ph, axes=[0]).named("x")
        ex = Executor()
        r = tfs.reduce_blocks(fetch, df, executor=ex)
        assert int(np.asarray(r)) == 21 // 5
        assert all(k[0] != "block-bucketed" for k in ex.cache_keys())


class TestEmptyBlocks:
    def test_repartition_beyond_nrows_reduce_min(self):
        # regression (ISSUE 3 satellite): zero-row blocks must never
        # dispatch — a padded all-pad block would emit +inf partials
        df = tfs.TensorFrame.from_dict(
            {"x": np.array([3.0, 1.0, 2.0], np.float32)}
        ).repartition(8)
        assert 0 in df.block_sizes()
        for op, want in (("min", 1.0), ("max", 3.0), ("sum", 6.0)):
            r = tfs.reduce_blocks(_reduce(df, op), df, executor=Executor())
            assert float(np.asarray(r)) == want

    def test_lazy_fused_reduce_skips_empty_blocks(self):
        df = tfs.TensorFrame.from_dict(
            {"x": np.array([3.0, 1.0, 2.0], np.float32)}
        ).repartition(6)
        lf = df.lazy().map_blocks((tfs.block(df, "x") * 2.0).named("y"))
        r = lf.reduce_blocks(_reduce(lf, "min", "y"))
        assert float(np.asarray(r)) == 2.0


class TestStreaming:
    def _fetch(self):
        first = tfs.TensorFrame.from_dict({"x": np.zeros(1, np.float32)})
        return _reduce(first, "sum")

    def test_varying_chunks_bounded_compiles_and_identical(self):
        sizes = [17, 33, 5, 64, 12, 100, 41, 9, 77, 28]
        chunks = [
            tfs.TensorFrame.from_dict(
                {"x": (np.arange(n) % 7).astype(np.float32)}
            )
            for n in sizes
        ]
        ex = Executor()
        # single-device bound (scheduler-off; see TestBucketedMap note)
        with tfs.config.override(block_scheduler="off"):
            r = tfs.reduce_blocks_stream(
                self._fetch(), iter(chunks), executor=ex
            )
        with tfs.config.override(shape_bucketing=False):
            r0 = tfs.reduce_blocks_stream(
                self._fetch(), iter(chunks), executor=Executor()
            )
        assert np.asarray(r) == np.asarray(r0)
        rungs = len({sp.bucket_for(n) for n in sizes})
        # per-chunk programs on the ladder + one final combine program
        assert ex.jit_shape_compiles() <= rungs + 1
        assert rungs < len(set(sizes))

    def test_lazy_chunks_stream_bucketed(self):
        sizes = [11, 29, 53]
        def chunks():
            for n in sizes:
                c = tfs.TensorFrame.from_dict(
                    {"x": (np.arange(n) % 5).astype(np.float32)}
                )
                yield c.lazy().map_blocks((tfs.block(c, "x") * 2.0).named("y"))
        first = tfs.TensorFrame.from_dict({"y": np.zeros(1, np.float32)})
        fetch = _reduce(first, "sum", "y")
        ex = Executor()
        r = tfs.reduce_blocks_stream(fetch, chunks(), executor=ex)
        want = sum(2.0 * float((np.arange(n) % 5).sum()) for n in sizes)
        assert float(np.asarray(r)) == want
        kinds = {k[0] for k in ex.cache_keys()}
        assert "block-bucketed" in kinds

    def test_empty_chunk_skipped(self):
        chunks = [
            tfs.TensorFrame.from_dict(
                {"x": (np.arange(n) % 7).astype(np.float32)}
            )
            for n in (9, 0, 21)
        ]
        r = tfs.reduce_blocks_stream(self._fetch(), iter(chunks))
        want = float((np.arange(9) % 7).sum() + (np.arange(21) % 7).sum())
        assert float(np.asarray(r)) == want

    def test_empty_pandas_chunk_skipped(self):
        pd = pytest.importorskip("pandas")
        chunks = [
            pd.DataFrame({"x": (np.arange(n) % 7).astype(np.float32)})
            for n in (4, 0, 3)
        ]
        r = tfs.reduce_blocks_stream(self._fetch(), iter(chunks))
        want = float((np.arange(4) % 7).sum() + (np.arange(3) % 7).sum())
        assert float(np.asarray(r)) == want

    def test_all_empty_stream_raises(self):
        chunks = [tfs.TensorFrame.from_dict({"x": np.zeros(0, np.float32)})]
        with pytest.raises(ValueError, match="zero rows"):
            tfs.reduce_blocks_stream(self._fetch(), iter(chunks))


class TestLazyFusion:
    def test_fused_chain_bucketed_matches_eager(self):
        df = _uneven([7, 19, 40, 13])
        ex = Executor()
        lf = df.lazy()
        lf = lf.map_blocks(
            (tfs.block(lf, "x") * 2.0 + 1.0).named("y"), executor=ex
        )
        r = lf.reduce_blocks(_reduce(lf, "sum", "y"), executor=ex)
        with tfs.config.override(shape_bucketing=False):
            ex0 = Executor()
            lf0 = df.lazy()
            lf0 = lf0.map_blocks(
                (tfs.block(lf0, "x") * 2.0 + 1.0).named("y"), executor=ex0
            )
            r0 = lf0.reduce_blocks(_reduce(lf0, "sum", "y"), executor=ex0)
        assert np.asarray(r) == np.asarray(r0)
        # whole chain = ONE bucketed per-block program + one combine
        from collections import Counter

        kinds = Counter(k[0] for k in ex.cache_keys())
        assert kinds["block-bucketed"] == 1
        assert kinds["block"] == 0

    def test_forced_map_plan_bucketed_bit_identical(self):
        df = _uneven([7, 19, 40, 13])
        ex = Executor()
        lf = df.lazy().map_blocks(
            (tfs.block(df, "x") * 3.0).named("z"), executor=ex
        )
        out = lf.force()
        np.testing.assert_array_equal(
            np.asarray(out["z"].values), df["x"].values * 3.0
        )
        assert ex.jit_shape_compiles() <= len(
            set(b for b in df.bucketed_block_sizes() if b)
        )


class TestWarmRerun:
    def test_rerun_adds_no_program_and_no_shape(self):
        """64 distinct block sizes through a map, three reduces and a
        fused chain: every program stays on the ladder, and a second
        pass on the warm executor compiles nothing and answers the
        same."""
        sizes = [5 + 3 * i for i in range(64)]
        df = _uneven(sizes, mod=251)

        def workload(ex):
            mapped = tfs.map_blocks(
                (tfs.block(df, "x") * 2.0 + 1.0).named("y"), df,
                executor=ex,
            )
            out = {"map": np.asarray(mapped["y"].values)}
            for op in ("sum", "min", "mean"):
                out[op] = np.asarray(
                    tfs.reduce_blocks(_reduce(df, op), df, executor=ex)
                )
            lf = df.lazy().map_blocks(
                (tfs.block(df, "x") * 3.0).named("z"), executor=ex
            )
            out["fused"] = np.asarray(
                lf.reduce_blocks(_reduce(lf, "sum", "z"), executor=ex)
            )
            return out

        ex = Executor()
        # single-device bound (scheduler-off; see TestBucketedMap note)
        with tfs.config.override(block_scheduler="off"):
            first = workload(ex)
            ladder = math.ceil(math.log2(max(sizes))) + 2
            per_program = [
                fn._cache_size() for fn in ex._cache.values()
                if callable(getattr(fn, "_cache_size", None))
            ]
            assert per_program and max(per_program) <= ladder, per_program
            misses, shapes = ex.cache_misses, ex.jit_shape_compiles()
            again = workload(ex)
        assert ex.cache_misses == misses
        assert ex.jit_shape_compiles() == shapes
        for k in first:
            np.testing.assert_array_equal(again[k], first[k])


class TestObservability:
    def test_executor_stats_has_shape_compiles(self):
        ex = Executor()
        df = _uneven([5, 12])
        tfs.map_blocks((tfs.block(df, "x") * 2.0).named("y"), df, executor=ex)
        s = executor_stats(ex)
        assert s["jit_shape_compiles"] >= s["compile_count"] >= 1
        assert s["jit_shape_compiles"] == ex.jit_shape_compiles()

    @staticmethod
    def _capture_storms():
        """The framework logger is propagate=False (utils.log), so caplog
        cannot see it — attach a recording handler directly."""
        records = []

        class _H(logging.Handler):
            def emit(self, record):
                if "recompile storm" in record.getMessage():
                    records.append(record)

        logger = logging.getLogger("tensorframes_tpu.executor")
        h = _H(level=logging.WARNING)
        logger.addHandler(h)
        return records, lambda: logger.removeHandler(h)

    def _drift(self, ex):
        for n in (10, 20, 30, 40, 50, 60, 70):
            df = tfs.TensorFrame.from_dict(
                {"x": np.arange(n, dtype=np.float32)}
            )
            tfs.map_blocks(
                (tfs.block(df, "x") * 2.0).named("y"), df, executor=ex
            )

    def test_recompile_storm_warns_once(self):
        records, detach = self._capture_storms()
        try:
            with tfs.config.override(
                shape_bucketing=False, recompile_warn_shapes=3
            ):
                self._drift(Executor())
        finally:
            detach()
        assert len(records) == 1  # one warning per program, ever

    def test_bucketing_quells_the_storm(self):
        records, detach = self._capture_storms()
        try:
            with tfs.config.override(recompile_warn_shapes=4):
                ex = Executor()
                self._drift(ex)
        finally:
            detach()
        assert not records
        assert ex.jit_shape_compiles() <= 4  # ladder rungs for 10..70


class TestMeshBucketing:
    def _mesh(self):
        import jax

        try:
            from tensorframes_tpu.parallel import data_mesh
        except Exception as e:  # jax pin without jax.shard_map
            pytest.skip(f"mesh layer unavailable: {e}")
        if len(jax.devices()) < 2:
            pytest.skip("needs the virtual multi-device CPU mesh")
        return data_mesh()

    def test_mesh_map_pads_to_uniform_shards(self):
        mesh = self._mesh()
        # nrows deliberately NOT divisible by ndev: unbucketed this would
        # run a main shard program + a remainder tail program
        df = tfs.TensorFrame.from_dict(
            {"x": (np.arange(103) % 11).astype(np.float32)}
        )
        ex = Executor()
        out = tfs.map_blocks(
            (tfs.block(df, "x") * 2.0 + 1.0).named("y"),
            df,
            mesh=mesh,
            executor=ex,
        )
        np.testing.assert_array_equal(
            np.asarray(out["y"].values), df["x"].values * 2.0 + 1.0
        )
        # bucketed: ONE padded shard_map dispatch, no tail "block" entry
        kinds = {k[0] for k in ex.cache_keys()}
        assert not any(k == "block" for k in kinds)

    def test_mesh_reduce_bucketed_shards_bounded_and_exact(self):
        mesh = self._mesh()
        ex = Executor()
        # drifting nrows: unbucketed this compiles one shard_map shape
        # per distinct nrows//ndev AND one tail shape per remainder
        for n in (103, 217, 311, 409, 97, 530):
            df = tfs.TensorFrame.from_dict(
                {"x": (np.arange(n) % 11).astype(np.float32)}
            )
            for op, want in (("min", 0.0), ("sum", None)):
                r = tfs.reduce_blocks(
                    _reduce(df, op), df, mesh=mesh, executor=ex
                )
                if want is None:
                    want = float((np.arange(n) % 11).sum())
                assert float(np.asarray(r)) == want
        rungs = len(
            {sp.bucket_for(-(-n // mesh.devices.size))
             for n in (103, 217, 311, 409, 97, 530)}
        )
        # two graphs (min/sum) x (sharded program + masked tail + the
        # rare combine), each bounded to the ladder, not to #distinct n
        assert ex.jit_shape_compiles() <= 2 * 3 * (rungs + 1)

    def test_mesh_reduce_allpad_shard_indirect_transform_exact(self):
        # nrows << ndev * rung forces all-pad shards; Max(Abs(x)) must
        # NOT see a -inf identity re-transformed to +inf in the combine
        # (indirect graphs fall back to unbucketed shards there)
        mesh = self._mesh()
        df = tfs.TensorFrame.from_dict(
            {"x": np.array([2.0, 5.0, 3.0], np.float32)}
        )
        ph = tfs.block(df, "x", tf_name="x_input")
        fetch = dsl.reduce_max(dsl.square(ph), axes=[0]).named("x")
        r = tfs.reduce_blocks(fetch, df, mesh=mesh, executor=Executor())
        with tfs.config.override(shape_bucketing=False):
            r0 = tfs.reduce_blocks(
                fetch, df, mesh=mesh, executor=Executor()
            )
        assert np.isfinite(np.asarray(r)).all()
        assert np.asarray(r) == np.asarray(r0)

    def test_mesh_reduce_mean_keeps_unbucketed_shards(self):
        # Mean must NOT regroup shard boundaries (equal-weight partial
        # combine); it keeps the plain sharded program + masked tail
        mesh = self._mesh()
        df = tfs.TensorFrame.from_dict(
            {"x": (np.arange(103) % 11).astype(np.float32)}
        )
        ex = Executor()
        r = tfs.reduce_blocks(_reduce(df, "mean"), df, mesh=mesh, executor=ex)
        with tfs.config.override(shape_bucketing=False):
            r0 = tfs.reduce_blocks(
                _reduce(df, "mean"), df, mesh=mesh, executor=Executor()
            )
        assert np.asarray(r) == np.asarray(r0)
        assert any(k[0].startswith("shred-") and "bkt" not in k[0]
                   for k in ex.cache_keys())

    def test_mesh_fused_force_bucketed(self):
        mesh = self._mesh()
        df = tfs.TensorFrame.from_dict(
            {"x": (np.arange(103) % 11).astype(np.float32)}
        )
        lf = df.lazy().map_blocks((tfs.block(df, "x") * 3.0).named("z"))
        out = lf.force(mesh=mesh)
        np.testing.assert_array_equal(
            np.asarray(out["z"].values), df["x"].values * 3.0
        )
