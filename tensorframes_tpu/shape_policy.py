"""Shape-bucketed block execution: bound XLA recompiles across ragged blocks.

The executor caches ONE lowered callable per ``(kind, graph, fetches,
feeds)`` key, but ``jax.jit`` still re-specializes (full XLA compile) for
every distinct concrete BLOCK SHAPE it sees — so uneven ``repartition``
remainders, filtered frames, and variable-size `reduce_blocks_stream`
chunks each pay full compile latency, which on TPU dwarfs the per-block
compute. A long-lived process whose block sizes drift is a recompile
storm the cache counters cannot even see (the same class of problem the
aggregate planner already solved for group sizes with its pow2 chunk
decomposition, and `_run_ragged_bucketed` solved for ragged cells).

This module is the block-level shape policy: every block feed is padded
up to a geometric row-bucket ladder (``config.shape_bucket_min`` *
``config.shape_bucket_growth``^k) by REPLICATING the last valid row, the
bucketed program executes, and the padding is removed semantically:

- map verbs slice the padded rows off every output (safe exactly when
  every fetch is a row-local transform — `rowwise_fetches` proves it
  with the same conservative op walk the aggregate chunk planner uses;
  anything else runs the ordinary unbucketed dispatch);
- per-block reduce stages mask the padded rows to the reduction
  identity at the TRANSFORM OUTPUT (sum→0, prod→1, min/max→±inf /
  integer extrema, mean via masked sum / true row count), so
  ``Sum(exp(x))`` stays exact — masking the *input* would feed
  ``exp(0)=1`` per pad row into the sum. Only graphs the structural
  classifier (`aggregate._chunk_combiners`) proves reducible this way
  are bucketed; the rest keep the exact unbucketed program.

Compile count per graph drops from O(#distinct block sizes) to
O(log_growth max-block-rows). Replicating the last row (instead of
zero-fill) keeps pad rows numerically ordinary, so ``check_numerics``
and non-total ops (Log, Reciprocal, ...) never see synthetic poison.

The block WINDOW (`block_feeds` / `unpad_block`, the per-block loops of
`api.map_blocks` and `api.map_rows`' dense route): the same argument
makes a block's neighbours in its own column as good as replicas of its
last row — they are real rows, so numerically ordinary, and a row-local
program never lets them touch the valid rows. Where every feed column
is a `jax.Array` resident on one device with at least a rung's rows, a
block off its rung is therefore not cut and padded by eager copies
(``values[lo:hi]``, ``a[-1:]``, ``broadcast_to``, ``concatenate``: four
Python-level jax dispatches, three of them with a host-to-device put of
a start index) but taken as ONE rung-sized window of the columns:
``dynamic_slice_in_dim(col, start, bucket)`` with ``start = min(lo,
N - bucket)`` a traced scalar, one jitted call for all feed columns and
one executable for every block of a rung (`block_window`). The valid
rows sit at ``[shift, shift + n)`` of the window, ``shift = lo -
start``: 0 except in blocks near the column's end. The program sees the
same rung shape as before, and one small jitted static slice
(`block_unpad`) takes ``[shift, shift + n)`` out of its outputs: three
dispatches a block, none eager, where there were six. What decides is
what the input shows, not a knob: a column shorter than the rung (every
single-block frame off a rung), a host (numpy) column, a column sharded
over devices or a block already on its rung keeps the cut, and off its
rung the replicated pad (a short resident column from its rung's
second size on: PROMOTION, below). The reduce, lazy, stream and mesh
routes call `pad_feeds` and never take a window.

PROMOTION (`block_dispatch`, the same two loops): the ladder trades pad
work for compiles: a padded block copies its column into a padded one,
computes up to growth times the rows and copies the valid rows out
again, and in return shares its rung's program with every other size
of the rung. A one-block frame off its rung has no window (its column
is shorter than the rung), so it is where that trade is made, and when
a shape has earned a program of its own is what the policy observes,
not a knob. Two rules, both kept per cached program on the executor's
cache entry (`ProgramLedger`, evicted with the entry), both for
device-resident feeds only:

THE FIRST SIZE of a rung is bought at price zero. Per rung, trailing
shapes, dtypes and device the ledger remembers the first row count it
was asked for (``ProgramLedger.rungs``), and a dispatch of that size
runs the program on the cut itself: ``bucket == n``, no ``shape.pad``,
no ``shape.unpad``, ``shape_bucketing.pad_rows`` 0,
``shape_bucketing.first_size_dispatch`` 1. The program's own `jax.jit`
specialises to the exact shape on the calling thread, where and for as
long as the rung's compile would have stood: a pad at first sight would
save a compile that is being paid at that very moment anyway. No
thread, no price and no bandwidth are needed, so the rule holds on a
device kind `costmodel.DEVICE_PEAKS` does not know too. The pad buys
something only when a second, different size arrives on the rung
(``shape_bucketing.rungs_widened`` counts the rungs where one did): that
size and every later one take the replicated pad, the rung's program
compiles then, once, and the first size keeps running exact (the jit
holds its executable). BOUND: per program at most one compile more per
rung than the ladder alone, the first size's, so compiles stay O(log
max-block-rows) however sizes drift; for a shape that repeats and is
its rung's first, the pad is never paid at all. The regret is that one
compile, and only on a rung that later sees another size.

RENT OR BUY, from a rung's second size on. Per exact feed signature
(row count, trailing shapes, dtypes, the device) a ledger line
(``ProgramLedger.shapes``; at most ``config.executor_cache_entries``
lines a program, least recently seen out first) adds up the RENT paid
so far: for every replicated pad, the seconds the pad
cost beyond an exact dispatch, from shapes alone: the bytes of the pad
copy (read ``n``, write ``bucket`` rows of every feed), of the ``bucket
- n`` pad rows through the program (feed and output row bytes) and of
the slice (read and write ``n`` rows of every output), over the
device's HBM bandwidth (`runtime.costmodel.device_peaks`; a device kind
the table does not know is never promoted, counted once a line as
``shape_bucketing.promotion_unpriced``). The PRICE is what this
program's own last XLA compile took, which `Executor._instrument`
measures whatever the telemetry switches say (a program with no
measured compile, the native host's, is never promoted). When the rent
reaches the price the shape is bought: the program's underlying
`jax.jit` is lowered and compiled ahead of time for the exact signature
on a thread of its own (span ``shape.promote``, kind ``compile``), and
calls keep taking the pad until the executable is there: the calling
thread never waits for a compile it did not wait for before. From then
on the block is dispatched on the cut itself, as a first size is, and
``shape_bucketing.promoted_dispatch`` counts 1 (beside ``promotions``,
shapes bought, and ``promotion_failed``: a compile that raised leaves
the shape on its pad for good). For any sequence of block sizes the
seconds spent compiling promotions never exceed the seconds already
lost to pads, so sizes that drift (none repeats: a call's rent each)
and small blocks (microseconds of pad against a compile) compile
what the ladder compiles and each rung's first size. The measured
price may be a fetch from the compile cache while the exact compile is
cold; that is why the compile may not run on the calling thread, and
why nothing else in the rule needs the price to be right. The promoted
trace reads ``config`` (matmul precision) as any new rung's compile
would: when it runs.

Windows, blocks on their rung, numpy or sharded columns, `block_feeds`
(no program), unbucketed callers and the routes that call `pad_feeds`
themselves are untouched by both rules; no knob decides any of it.

BLOCK GROUP (`block_runs` / `group_dispatch`, the same two loops, on one
device: no scheduler, or a scheduler whose home plan keeps the blocks
on the device that holds their columns, `runtime.scheduler`): a frame
cut into equal blocks asks the host for
the same work once a block, and for a small block that work (a window,
a dispatch, an unpad, their spans and checks: about a millisecond) is
many times the program's. Where every feed column is a `jax.Array`
resident on one device, each maximal run of two or more non-empty
blocks of one size ``n`` (empty blocks between them hold no rows and
do not end it) is therefore dispatched ONCE, as ONE PASS of the
program's own `jax.jit` over the run's rows:
``dynamic_slice_in_dim(col, lo, k * n)`` of every feed column (``lo`` a
traced scalar, so a run anywhere in a longer column shares the
executable; where the run is the whole column XLA drops the slice),
and the outputs are the program's at ``k * n`` rows. The proof is the
one the ladder's pad rests on: both callers group only programs proven
row-local (output row i depends on input row i alone), so the run's
``k`` blocks give, row for row, what one pass over rows ``[lo, lo + k *
n)`` gives; block boundaries are something such a program cannot see,
as it cannot see pad rows, and the output frame keeps the input's
offsets. No window, no pad row (``shape_bucketing.pad_rows`` counts 0),
no unpad, no loop and no carried output on the device, and for a frame
that is one run no concat. ``shape_bucketing.group_dispatch`` counts
the groups, ``shape_bucketing.grouped_blocks`` the blocks they covered.
The group's function takes the program's function's name, so its XLA
module is named as the program's is (a graph's: ``jit_fn``). What
decides is what the input shows: a block whose size no neighbour
shares, numpy or sharded columns, a column too long for an int32 row
index, a program with no ledger and a program whose outputs do not
keep the run's rows run block by block as before, and so does every
block a scheduler spread over devices (an explicit ``devices=``, or a
plan with no home), a trim or bound values (the caller's side,
`api._run_blocks`). One pass holds the program's temporaries at ``k *
n`` rows where a block holds them at ``n``: a RESOURCE fault in a group
sends its run back to the block loop, which may split rows (and, under
a schedule, so does a transient fault: the block loop owns failover).
BOUND: a group executable is specific to the run's rows ``k * n`` (4 x
10 and 5 x 8 share one), the columns' whole shapes and dtypes and the
device, and is compiled on the calling thread at first sight, where
the per-block program for a new ``n`` would have compiled at that
moment anyway. The lines are kept on the program's ledger
(``ProgramLedger.groups``: evicted with the cache entry, at most
``config.executor_cache_entries`` lines a program, least recently seen
out first), so a program holds no more group executables than that. A
ledger that is full has seen that many distinct runs: the process
drifts, and from then on a new signature takes the line of the least
recently seen one but must come back while it holds it before it is
compiled (until then its blocks run one by one, on the ladder). So a
program compiles, beside the ladder's O(log max-block-rows) and each
rung's first size, at most ``config.executor_cache_entries`` groups at
first sight and after that only groups that have repeated: runs that
never repeat stop compiling when the ledger is full.

Exactness: map outputs, min/max, and integer-dtype reductions are
bit-identical to unbucketed eager execution. Float sum/mean reduce over
a wider (padded) axis, so XLA's vectorized accumulation may group the
REAL elements differently — the identical reassociation tolerance the
repo already documents for `_aggregate_segment` and for stacking block
partials; integer-valued float data stays bit-exact. Disable with
``config.update(shape_bucketing=False)`` when exact FP accumulation
order matters more than bounded compiles.
"""

from __future__ import annotations

import functools
import math
import threading
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from .aggregate import _chunk_combiners, _rowwise_transform
from .graph.ir import Graph, base_name as _base
from .ops.lowering import build_callable
from .runtime import costmodel as _cm
from .runtime import executor as _ex
from .utils import telemetry as _tele
from .utils.profiling import count as _count

__all__ = [
    "bucket_for",
    "bucket_ladder",
    "enabled",
    "observe_fill",
    "exact_dispatch",
    "pad_feeds",
    "pad_lead",
    "slice_pad_rows",
    "block_feeds",
    "unpad_block",
    "BlockDispatch",
    "block_dispatch",
    "block_runs",
    "group_dispatch",
    "drain",
    "rowwise_fetches",
    "MaskPlan",
    "masked_reduce_plan",
    "fused_mask_plan",
    "build_masked_reduce",
    "masked_callable",
    "dispatch_masked",
]


# ---------------------------------------------------------------------------
# bucket ladder
# ---------------------------------------------------------------------------


def bucket_for(
    n: int,
    growth: Optional[float] = None,
    min_bucket: Optional[int] = None,
) -> int:
    """Smallest ladder rung >= ``n``: ``min_bucket * growth^k`` rounded
    up to an int (each rung strictly larger than the last, so the
    ladder is finite for any growth > 1). ``n <= 0`` maps to 0 — empty
    blocks are never dispatched, bucketed or not."""
    from . import config as _config

    cfg = _config.get()
    g = float(growth if growth is not None else cfg.shape_bucket_growth)
    b = int(min_bucket if min_bucket is not None else cfg.shape_bucket_min)
    if g <= 1.0:
        raise ValueError(f"shape_bucket_growth must be > 1, got {g}")
    if b < 1:
        raise ValueError(f"shape_bucket_min must be >= 1, got {b}")
    if n <= 0:
        return 0
    while b < n:
        b = max(b + 1, int(-(-b * g // 1)))  # ceil(b * g), monotone
    return b


def bucket_ladder(
    max_rows: int,
    growth: Optional[float] = None,
    min_bucket: Optional[int] = None,
) -> List[int]:
    """The distinct rungs covering block sizes 1..max_rows — the bound
    on compiled shape specializations per program (the tests assert
    against its length)."""
    rungs: List[int] = []
    n = 1
    while n <= max_rows:
        r = bucket_for(n, growth, min_bucket)
        rungs.append(r)
        n = r + 1
    return rungs


def enabled(executor=None) -> bool:
    """Bucketing is on for this dispatch: the config knob is set AND the
    executor opts in (`supports_bucketing`; both the in-process and the
    native executor do — the native host's per-shape-signature compile
    cache benefits identically)."""
    from . import config as _config

    if not _config.get().shape_bucketing:
        return False
    return executor is None or getattr(executor, "supports_bucketing", False)


# ---------------------------------------------------------------------------
# padding
# ---------------------------------------------------------------------------


def pad_lead(a, n: int, bucket: int):
    """Pad ``a``'s lead dim from ``n`` to ``bucket`` rows by replicating
    the last valid row (numerically ordinary pad rows — see module
    docstring). Device arrays pad with jnp (async, stays on device);
    host arrays with numpy."""
    if bucket <= n:
        return a
    import jax

    rep = (bucket - n,) + tuple(a.shape[1:])
    if isinstance(a, jax.Array):
        import jax.numpy as jnp

        return jnp.concatenate([a, jnp.broadcast_to(a[-1:], rep)])
    a = np.asarray(a)
    return np.concatenate([a, np.broadcast_to(a[-1:], rep)])


def observe_fill(n: int, bucket: int, verb: Optional[str] = None) -> None:
    """Record one bucketed dispatch's fill fraction (valid rows /
    rung rows) into the ``bucket_fill{verb=}`` histogram — exact-rung
    hits observe 1.0, so the distribution is the honest per-verb
    bucket-economics signal the workload profile and the future ladder
    autotuner consume. Gated on the telemetry master switch like every
    histogram; the verb label rides the ambient verb span."""
    if bucket <= 0 or not _tele.enabled():
        return
    if verb is None:
        verb = _tele.current_verb() or "unattributed"
    _tele.histogram_observe(
        "bucket_fill", min(1.0, n / bucket), verb=verb
    )


def exact_dispatch() -> None:
    """A block dispatched at its exact shape computes no pad row, and the
    counter says so: a reader of ``shape_bucketing.pad_rows`` tells "none"
    (0) from a program that does not count them (no such counter)."""
    _count("shape_bucketing.pad_rows", 0)


def pad_feeds(feeds: Sequence, n: int) -> Tuple[List, int]:
    """Pad every feed's lead dim up to ``n``'s bucket. Returns
    ``(padded_feeds, bucket)``; when ``bucket == n`` the feeds pass
    through untouched (the already-on-a-rung fast path)."""
    b = bucket_for(n)
    observe_fill(n, b)
    if b == n:
        return list(feeds), n
    _count("shape_bucketing.padded_dispatch")
    # pad waste observability: total synthetic rows dispatched (the
    # price paid for the bounded compile count — `diagnostics` readers
    # compare this against real row counters)
    _count("shape_bucketing.pad_rows", b - n)
    with _tele.span("shape.pad", rows=n, bucket=b):
        return [pad_lead(f, n, b) for f in feeds], b


def mesh_shard_plan(nrows: int, ndev: int):
    """Rung size + per-shard valid row counts for splitting ``nrows``
    into ``ndev`` contiguous bucket-rung shards — pure arithmetic, no
    data movement, so callers can decide ELIGIBILITY (e.g. the all-pad-
    shard gate in the mesh reduce) before paying for padded copies.
    ``valids[d]`` is 0 for shards that would be pure padding."""
    s = bucket_for(-(-nrows // ndev))
    valids = np.clip(nrows - s * np.arange(ndev), 0, s).astype(np.int32)
    return s, valids


def pad_mesh_shards(frame, cols_used: Sequence[str], ndev: int):
    """THE mesh padding recipe every bucketed `shard_map` verb shares:
    pad each used column so the frame splits into ``ndev`` contiguous
    shards of exactly one bucket rung (`mesh_shard_plan`) — `shard_map`
    then sees ONE static shape per rung and the varying ``rows % ndev``
    remainder-tail program disappears. Returns ``(main, tail,
    shard_rows, shard_valids)``; ``tail`` is empty by construction."""
    s, valids = mesh_shard_plan(frame.nrows, ndev)
    observe_fill(frame.nrows, s * ndev)
    main = {
        c: pad_lead(frame.column(c).values, frame.nrows, s * ndev)
        for c in set(cols_used)
    }
    tail = {c: main[c][:0] for c in main}
    return main, tail, s, valids


def slice_pad_rows(outs: Sequence, n: int, bucket: int) -> List:
    """Slice the pad rows back off a padded map dispatch's outputs (lazy
    device slices). An output that did not preserve the padded lead dim
    is returned untouched, so the caller's row-count validation can name
    it instead of a slice masking the contract violation."""
    if bucket == n:
        return list(outs)
    with _tele.span("shape.unpad", rows=n, bucket=bucket):
        return [
            o[:n] if getattr(o, "ndim", 0) and o.shape[0] == bucket else o
            for o in outs
        ]


# ---------------------------------------------------------------------------
# the block window: cut + pad in one program, unpad in another
# ---------------------------------------------------------------------------

# The two helpers' XLA modules take these functions' names
# (`jit_block_window`, `jit_block_unpad`): a device trace tells them from
# the verb's own program, `jit_fn`, by name.


@functools.partial(jax.jit, static_argnums=0)
def block_window(bucket: int, start, *columns):
    """``bucket`` rows of every column from row ``start`` on. ``start``
    is traced, so all blocks of one rung over one frame share an
    executable."""
    return tuple(
        jax.lax.dynamic_slice_in_dim(c, start, bucket) for c in columns
    )


@functools.partial(jax.jit, static_argnums=(0, 1))
def block_unpad(shift: int, n: int, *outs):
    """Rows ``[shift, shift + n)`` of every output: a static slice, one
    executable per (output shapes, shift, n)."""
    return tuple(jax.lax.slice_in_dim(o, shift, shift + n) for o in outs)


_INT32_MAX = int(np.iinfo(np.int32).max)


def _resident_device(columns: Sequence):
    """The one device every feed column lives on, each a `jax.Array`;
    else None (a numpy column, a sharded one, columns apart)."""
    devices = None
    for c in columns:
        if not isinstance(c, jax.Array):
            return None
        held = c.sharding.device_set
        if len(held) != 1 or (devices is not None and held != devices):
            return None
        devices = held
    return next(iter(devices)) if devices else None


def block_feeds(
    columns: Sequence, lo: int, hi: int, cut: Callable[[], List]
) -> Tuple[List, int, Optional[int]]:
    """The feeds of a bucketed map dispatch over rows ``[lo, hi)`` of
    the feed ``columns``: ``(feeds, bucket, shift)``, every feed
    ``bucket`` rows long.

    Where the columns have a window (module docstring) and the block is
    off its rung, the feeds are that window and the valid rows are
    ``[shift, shift + n)`` of it. Otherwise the caller's ``cut()`` (its
    ``values[lo:hi]`` per feed) is padded by `pad_feeds` and ``shift``
    is None. `unpad_block` takes the same triple back. `block_dispatch`
    with no program to promote."""
    d = block_dispatch(None, columns, lo, hi, cut)
    return d.feeds, d.bucket, d.shift


def unpad_block(
    outs: Sequence, n: int, bucket: int, shift: Optional[int]
) -> List:
    """The ``n`` valid rows of a `block_feeds` dispatch's outputs. A
    replicated pad (``shift`` None) goes to `slice_pad_rows`; a window's
    device outputs go through `block_unpad` in one call. As there, an
    output that did not keep the padded lead dim is returned
    untouched."""
    if shift is None:
        return slice_pad_rows(outs, n, bucket)
    with _tele.span("shape.unpad", rows=n, bucket=bucket):
        outs = list(outs)
        padded = [
            i for i, o in enumerate(outs)
            if getattr(o, "ndim", 0) and o.shape[0] == bucket
        ]
        if all(isinstance(outs[i], jax.Array) for i in padded):
            cut = block_unpad(shift, n, *[outs[i] for i in padded])
        else:  # a host executor's numpy outputs: views
            cut = [outs[i][shift:shift + n] for i in padded]
        for i, o in zip(padded, cut):
            outs[i] = o
        return outs


# ---------------------------------------------------------------------------
# promotion: a rung's first size, and a pad that keeps coming back, run
# at their exact shape
# ---------------------------------------------------------------------------


class BlockDispatch:
    """One bucketed map dispatch as `block_dispatch` planned it: the
    ``feeds`` (``bucket`` rows each), what to ``call`` on them, and
    `unpad` for its outputs."""

    __slots__ = ("feeds", "bucket", "shift", "call", "rows", "book", "line")

    def __init__(self, feeds, bucket, shift, call, rows, book=None,
                 line=None):
        self.feeds = feeds
        self.bucket = bucket
        self.shift = shift
        self.call = call
        self.rows = rows
        self.book = book  # a replicated pad of a program that keeps a
        self.line = line  # ledger: the ledger, and the line the rent goes on

    def unpad(self, outs: Sequence) -> List:
        """The valid rows of the dispatch's outputs (`unpad_block`). A
        replicated pad that came this far pays its rent first."""
        if self.line is not None:
            self.line.pay(self.book, self.rows, self.bucket, self.feeds, outs)
        return unpad_block(outs, self.rows, self.bucket, self.shift)


def block_dispatch(
    program: Optional[Callable],
    columns: Sequence,
    lo: int,
    hi: int,
    cut: Callable[[], List],
    device=None,
) -> BlockDispatch:
    """`block_feeds` for a dispatch of the executor's cached ``program``
    on ``device`` (None: where the columns live), with promotion (module
    docstring): a block that would take the replicated pad of resident
    columns is looked up in the program's ledger. The first size its
    rung was asked for, and a later one once its rent has bought its
    executable, is dispatched on the ``cut()`` itself (``bucket == n``,
    nothing to unpad): the first on ``program``, which compiles for it
    as it would have for the rung, the bought one on its executable.
    Any other is the pad, and `unpad` charges the rent."""
    n = hi - lo
    b = bucket_for(n)
    resident = None if b == n else _resident_device(columns)
    # a window: a rung's rows in every column, and few enough for an
    # int32 start index
    if resident is not None and (
        b <= min(c.shape[0] for c in columns) <= _INT32_MAX
    ):
        observe_fill(n, b)
        _count("shape_bucketing.window_dispatch")
        # rows computed beyond the real ones, as `pad_feeds` counts them
        _count("shape_bucketing.pad_rows", b - n)
        start = min(lo, columns[0].shape[0] - b)
        with _tele.span("shape.pad", rows=n, bucket=b):
            feeds = block_window(b, np.int32(start), *columns)
        return BlockDispatch(list(feeds), b, lo - start, program, n)
    book = line = None
    if resident is not None:
        book = _program_ledger(program)
    if book is not None:
        on = resident if device is None else device
        trailing = tuple(
            (tuple(c.shape[1:]), np.dtype(c.dtype)) for c in columns
        )
        call = None
        if _first_size(book, trailing, n, b, on):
            _count("shape_bucketing.first_size_dispatch")
            call = program
        else:
            line = _line(book, trailing, n, on)
            if line.exact is not None:
                _count("shape_bucketing.promoted_dispatch")
                # handed out as the executor hands its programs out
                call = _ex.hand_out(line.exact, book.key)
        if call is not None:
            observe_fill(n, n)
            exact_dispatch()
            return BlockDispatch(cut(), n, None, call, n)
    feeds, b = pad_feeds(cut(), n)
    return BlockDispatch(feeds, b, None, program, n, book, line)


_ledger_lock = threading.Lock()


def _row_bytes(arrays: Sequence) -> int:
    """Bytes of one row (a step along the lead dim) of all of them."""
    return sum(
        math.prod(a.shape[1:]) * np.dtype(a.dtype).itemsize for a in arrays
    )


def _compile_exact(jitted, avals: Sequence, device):
    """``jitted`` (a `jax.jit`) lowered and compiled ahead of time for
    feeds of ``avals`` (``(shape, dtype)`` each) on ``device``."""
    sharding = jax.sharding.SingleDeviceSharding(device)
    return jitted.lower(*[
        jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        for shape, dtype in avals
    ]).compile()


def _program_ledger(program):
    """The `ProgramLedger` of the executor's cache entry behind
    ``program`` (itself, or under a fault injector's wrapper). None for
    the native host's programs, plain callables, and no program."""
    while program is not None and not hasattr(program, "ledger"):
        program = getattr(program, "__wrapped__", None)
    return None if program is None else program.ledger


def _first_size(book, trailing: Tuple, n: int, bucket: int, device) -> bool:
    """Whether ``n`` rows is the first size the ledger ``book`` was
    asked for on the rung ``bucket`` of feeds of ``trailing`` (shape
    past the rows and dtype, each) on ``device``: recorded at first
    sight, one entry a rung (so no more than a ladder's worth a
    signature and device). A second size counts its rung as widened,
    once."""
    with _ledger_lock:
        seen = book.rungs.setdefault((bucket, trailing, device), [n, False])
        if seen[0] == n:
            return True
        newly_widened = not seen[1]
        seen[1] = True
    if newly_widened:
        _count("shape_bucketing.rungs_widened")
    return False


def _ledger_lines() -> int:
    """How many lines a program's ledger keeps of a kind (``shapes``,
    ``groups``): as many as the executor's cache keeps programs."""
    from . import config as _config

    return max(1, int(_config.get().executor_cache_entries))


def _line(book, trailing: Tuple, n: int, device) -> "_Line":
    """The line of the ledger ``book`` for ``n`` rows of feeds of
    ``trailing`` on ``device``, made at first sight. A ledger holds as
    many lines as the executor's cache holds programs
    (``config.executor_cache_entries``), least recently seen out first,
    so sizes that never come back cost no memory either."""
    avals = tuple(((n,) + shape, dtype) for shape, dtype in trailing)
    sig = (avals, device)
    with _ledger_lock:
        line = book.shapes.get(sig)
        if line is not None:
            book.shapes.move_to_end(sig)
            return line
        line = book.shapes[sig] = _Line(n, avals, device)
        while len(book.shapes) > _ledger_lines():
            book.shapes.popitem(last=False)
    return line


class _Line:
    """One line of a cached program's ledger (`ProgramLedger.shapes`,
    on the executor's cache entry): an exact feed signature,
    the seconds its replicated pads have cost so far (``rent``), and
    what they bought (``exact``, once ``thread`` has compiled it).
    ``closed`` lines are never bought: the compile failed, or the
    device has no known bandwidth to price a pad with."""

    __slots__ = ("rows", "avals", "device", "rent", "exact", "thread",
                 "closed")

    def __init__(self, rows, avals, device):
        self.rows = rows
        self.avals = avals
        self.device = device
        self.rent = 0.0
        self.exact = None
        self.thread = None
        self.closed = False

    def pay(self, book, n: int, bucket: int, feeds: Sequence,
            outs: Sequence) -> None:
        """Charge one padded dispatch: the seconds its pad cost beyond
        an exact dispatch, from shapes alone (module docstring), over
        the device's HBM bandwidth. When the rent so far reaches the
        price (the program's last XLA compile, ``book.compile_seconds``),
        buy: start the compile, off this thread."""
        if self.closed or self.thread is not None:
            return
        bandwidth = _cm.device_peaks(self.device)["hbm_bytes_s"]
        if not bandwidth:
            self.closed = True
            _count("shape_bucketing.promotion_unpriced")
            return
        fed = _row_bytes(feeds)
        out = _row_bytes(
            [o for o in outs if getattr(o, "ndim", 0) and o.shape[0] == bucket]
        )
        moved = (
            (n + bucket) * fed  # the pad copy: read n rows, write bucket
            + (bucket - n) * (fed + out)  # the pad rows through the program
            + 2 * n * out  # the slice: read and write n rows
        )
        price = book.compile_seconds
        with _ledger_lock:
            self.rent += moved / bandwidth
            if price is None or self.rent < price or self.thread is not None:
                return
            self.thread = threading.Thread(
                target=self._buy,
                args=(book.jitted, book.key, price),
                name="tfs-promote",
            )
        self.thread.start()

    def _buy(self, jitted, key: Tuple, price: float) -> None:
        """Lower and compile the program's own `jax.jit` for the exact
        signature, ahead of time: the same function, so the same XLA
        module name. A thread of its own; not a daemon, so an
        interpreter that exits waits for the compiler instead of
        tearing the backend down under it."""
        try:
            with _tele.span(
                "shape.promote", kind="compile", program=str(key[1]),
                rows=self.rows, rent=self.rent, price=price,
            ):
                compiled = _compile_exact(jitted, self.avals, self.device)
        except Exception as e:
            self.closed = True
            _count("shape_bucketing.promotion_failed")
            from .utils.log import get_logger

            get_logger("shape_policy").warning(
                "promotion of program %s/%s to %d rows failed, the shape "
                "stays on its padded rung: %s: %s",
                key[0], str(key[1])[:12], self.rows, type(e).__name__, e,
            )
            return
        devices = {self.device}

        def exact(*feeds):
            # feeds re-placed since the plan (a scheduler's failover)
            # are not what this was compiled for: the jit itself
            if any(f.sharding.device_set != devices for f in feeds):
                return jitted(*feeds)
            out = compiled(*feeds)
            _cm.note_exec(key, feeds, out)  # as `_instrument` counts one
            return out

        self.exact = exact
        _count("shape_bucketing.promotions")


def drain(executor=None, timeout: Optional[float] = None) -> bool:
    """Join the promotion compiles in flight on ``executor``'s programs
    (default: the default executor), each for at most ``timeout``
    seconds. True when none is still running. The teardown of the
    threads this module starts: for a test, so that no compile outlives
    it, or a benchmark that wants the next dispatch to be the promoted
    one; nothing in the package waits."""
    ex = executor or _ex.default_executor()
    programs = ex.programs() if hasattr(ex, "programs") else []
    with _ledger_lock:
        threads = [
            line.thread
            for book in map(_program_ledger, programs)
            if book is not None
            for line in book.shapes.values()
            if line.thread is not None
        ]
    for t in threads:
        t.join(timeout)
    return not any(t.is_alive() for t in threads)


# ---------------------------------------------------------------------------
# the block group: a run of equal blocks is one pass over the run's rows
# ---------------------------------------------------------------------------


def block_runs(
    columns: Sequence, offsets: Sequence[int]
) -> Dict[int, Tuple[int, int, int]]:
    """The runs of equal blocks a group can take (module docstring):
    ``{first block: (n, k, end)}`` for each maximal run of ``k >= 2``
    non-empty blocks of ``n`` rows each among the blocks ``offsets``
    cuts, ``end`` one past its last block. Empty blocks inside a run
    belong to it. Nothing unless every column is a `jax.Array` resident
    on one device and short enough for an int32 row index."""
    runs: Dict[int, Tuple[int, int, int]] = {}
    if _resident_device(columns) is None or (
        max(c.shape[0] for c in columns) > _INT32_MAX
    ):
        return runs
    first = end = n = k = 0
    for bi, (lo, hi) in enumerate(zip(offsets, offsets[1:])):
        if hi == lo:
            continue
        if hi - lo == n:
            k, end = k + 1, bi + 1
            continue
        if k >= 2:
            runs[first] = (n, k, end)
        first, end, n, k = bi, bi + 1, hi - lo, 1
    if k >= 2:
        runs[first] = (n, k, end)
    return runs


def group_dispatch(
    program: Optional[Callable], columns: Sequence, lo: int, n: int, k: int
) -> Optional[Callable]:
    """What to call on the feed ``columns`` themselves to run the
    executor's cached ``program`` over the ``k`` blocks of ``n`` rows
    from row ``lo`` on as one group: it returns the program's outputs
    over those ``k * n`` rows, from one pass. None where the run has no
    group executable (a program with no ledger, outputs that do not keep
    the run's rows, a compile that raised, a signature a full ledger has
    seen once): the caller dispatches the run block by block."""
    book = _program_ledger(program)
    if book is None:
        return None
    sig = (
        k * n,
        tuple((tuple(c.shape), np.dtype(c.dtype)) for c in columns),
        _resident_device(columns),
    )
    call = _group(book, sig)
    if call is None:
        return None
    observe_fill(k * n, k * n)
    _count("shape_bucketing.group_dispatch")
    _count("shape_bucketing.grouped_blocks", k)
    exact_dispatch()
    # handed out as the executor hands its programs out
    return functools.partial(_ex.hand_out(call, book.key), np.int32(lo))


class _Group:
    """One line of ``ProgramLedger.groups``: the executable of one run
    signature (``call``, None until compiled); ``closed`` once it is
    known that there will be none."""

    __slots__ = ("call", "closed")

    def __init__(self):
        self.call = None
        self.closed = False


def _group(book, sig: Tuple) -> Optional[Callable]:
    """The group executable of the ledger ``book`` for the run signature
    ``sig`` (the run's rows, the columns' shapes and dtypes, the device),
    compiled here at first sight while the ledger has room, and once it
    is full at second sight (module docstring, BOUND)."""
    limit = _ledger_lines()
    with _ledger_lock:
        line = book.groups.get(sig)
        if line is not None:
            book.groups.move_to_end(sig)
            build = line.call is None and not line.closed  # it came back
        else:
            build = len(book.groups) < limit
            line = book.groups[sig] = _Group()
            while len(book.groups) > limit:
                book.groups.popitem(last=False)
    if build:
        try:
            line.call = _compile_group(book, *sig)
        except Exception as e:
            from .utils.log import get_logger

            get_logger("shape_policy").warning(
                "no group executable for program %s/%s over a run of %d "
                "rows, the run is dispatched block by block: %s: %s",
                book.key[0], str(book.key[1])[:12], sig[0],
                type(e).__name__, e,
            )
        line.closed = line.call is None
    return line.call


def _compile_group(
    book, rows: int, avals: Sequence, device
) -> Optional[Callable]:
    """Lower and compile one pass of the ledger's program over ``rows``
    rows, from a traced start on, of columns of ``avals`` (``(shape,
    dtype)`` each) on ``device``, ahead of time. None where an output of
    the program does not keep the run's rows."""
    jitted, key = book.jitted, book.key

    def fn(lo, *columns):
        return jitted(*[
            jax.lax.dynamic_slice_in_dim(c, lo, rows) for c in columns
        ])

    # the XLA module is named after the function: as the program's own
    fn.__name__ = fn.__qualname__ = getattr(jitted, "__name__", "fn")
    feeds = [((), np.dtype(np.int32)), *avals]
    outs = jax.eval_shape(
        fn, *[jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in feeds]
    )
    if not isinstance(outs, (tuple, list)) or not all(
        getattr(o, "ndim", 0) and o.shape[0] == rows for o in outs
    ):
        return None
    t0 = time.perf_counter()
    compiled = _compile_exact(jax.jit(fn), feeds, device)
    t1 = time.perf_counter()
    _tele.record_compile(key[1], key[0], t1 - t0, "xla", t0, t1)

    def call(lo, *columns):
        out = compiled(lo, *columns)
        _cm.note_exec(key, columns, out)  # as `_instrument` counts one
        return out

    return call


# ---------------------------------------------------------------------------
# map-safety classification (row-local graphs)
# ---------------------------------------------------------------------------


def rowwise_fetches(
    graph: Graph, fetches: Sequence[str], ph_ranks: Dict[str, int]
) -> bool:
    """True when every fetch is a row-local function of the placeholders:
    output row i depends only on input rows i (and on sub-lead-rank
    constants), so pad rows cannot perturb valid rows and slicing the
    output is a faithful inverse of padding the input. Delegates to the
    ONE shared walk (`aggregate._rowwise_transform` — the same check
    the chunk planner runs on reduce transforms), so map-bucketing
    eligibility cannot diverge from reduce-chunk eligibility. Anything
    unrecognized (reductions, matmuls, reshapes, control flow)
    conservatively disqualifies the graph; it simply runs unbucketed."""
    return _rowwise_transform(graph, list(fetches), ph_ranks.get)


# ---------------------------------------------------------------------------
# masked per-block reduce
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MaskPlan:
    """Per-fetch recipe for the masked bucketed reduce program: the edge
    feeding each root reduce node (the rowwise-transform output) and the
    reduction's monoid tag."""

    roots: Tuple[str, ...]
    combiners: Tuple[str, ...]


def _root_edge(graph: Graph, fetch: str) -> str:
    src, idx = graph[_base(fetch)].data_inputs()[0]
    return f"{src}:{idx}" if idx else src


def masked_reduce_plan(
    graph: Graph, fetch_list: Sequence[str], summary
) -> Optional[MaskPlan]:
    """Classify a reduce graph for bucketed execution. Piggybacks on the
    aggregate chunk classifier: every fetch must be a recognized monoid
    reduce (Sum/Min/Max/Prod, float Mean) over the lead axis of a
    row-local transform of its placeholder. Returns None (→ run the
    exact unbucketed program) otherwise."""
    combs = _chunk_combiners(graph, list(fetch_list), summary)
    if combs is None:
        return None
    return MaskPlan(
        tuple(_root_edge(graph, f) for f in fetch_list),
        tuple(combs[_base(f)] for f in fetch_list),
    )


def fused_mask_plan(
    fused_graph: Graph,
    fused_fetches: Sequence[str],
    combiners: Sequence[str],
    ph_ranks: Dict[str, int],
) -> Optional[MaskPlan]:
    """Mask plan for a FUSED lazy chain ending in a classified reduce:
    the reduce classification ran on the plain reduce graph, but in the
    fused graph each reduce root consumes the whole pending map chain —
    masking at that root is only valid when the chain is row-local, so
    the walk re-runs over the fused graph."""
    roots = [_root_edge(fused_graph, f) for f in fused_fetches]
    if not rowwise_fetches(fused_graph, roots, ph_ranks):
        return None
    return MaskPlan(tuple(roots), tuple(combiners))


def _mask_identity(comb: str, dtype):
    """The reduction identity pad rows mask to, dtype-aware (floats get
    ±inf for min/max, integers their extrema, bools the monoid unit)."""
    if comb in ("sum", "mean"):
        return np.zeros((), dtype)
    if comb == "prod":
        return np.ones((), dtype)
    dt = np.dtype(dtype)
    if comb == "min":
        if dt.kind == "b":
            return np.ones((), dt)  # True: the AND/min identity
        if dt.kind in ("i", "u"):
            return np.asarray(np.iinfo(dt).max, dt)
        return np.asarray(np.inf, dt)
    if comb == "max":
        if dt.kind == "b":
            return np.zeros((), dt)
        if dt.kind in ("i", "u"):
            return np.asarray(np.iinfo(dt).min, dt)
        return np.asarray(-np.inf, dt)
    raise AssertionError(f"unknown combiner {comb!r}")


def build_masked_reduce(
    graph: Graph, plan: MaskPlan, feed_names: Sequence[str]
):
    """Build ``fn(valid, *feeds) -> tuple(partials)``: run the rowwise
    transforms on the (padded) block, mask rows >= ``valid`` to each
    fetch's reduction identity, reduce over the lead axis. ``valid`` is
    a traced scalar, so ONE compiled program serves every true row count
    within a bucket. The reductions mirror the eager lowerings
    (`ops.standard`): Sum/Prod keep the input dtype, Mean divides the
    masked sum by the true count (the classifier already rejected
    integer Mean)."""
    raw = build_callable(graph, list(plan.roots), list(feed_names))
    combiners = plan.combiners

    def fn(valid, *feeds):
        import jax.numpy as jnp

        valid = jnp.asarray(valid).reshape(())  # shard callers pass (1,)
        outs = raw(*feeds)
        res = []
        for comb, o in zip(combiners, outs):
            o = jnp.asarray(o)
            m = (jnp.arange(o.shape[0]) < valid).reshape(
                (-1,) + (1,) * (o.ndim - 1)
            )
            masked = jnp.where(m, o, _mask_identity(comb, o.dtype))
            if comb == "sum":
                res.append(jnp.sum(masked, axis=0, dtype=o.dtype))
            elif comb == "mean":
                s = jnp.sum(masked, axis=0, dtype=o.dtype)
                # multiply by the reciprocal, NOT a true divide: the
                # eager `jnp.mean` divides by a compile-time constant
                # count, which XLA strength-reduces to multiplication by
                # the rounded reciprocal — reproducing that keeps masked
                # means bit-identical to eager ones
                res.append(
                    s * (jnp.asarray(1.0, o.dtype) / jnp.asarray(valid, o.dtype))
                )
            elif comb == "prod":
                res.append(jnp.prod(masked, axis=0, dtype=o.dtype))
            elif comb == "min":
                res.append(jnp.min(masked, axis=0))
            else:
                res.append(jnp.max(masked, axis=0))
        return tuple(res)

    return fn


def masked_callable(ex, graph: Graph, fetch_list, feed_names, plan: MaskPlan):
    """THE "block-bucketed" program constructor — every masked dispatch
    site (eager reduce_blocks, the fused lazy reduce terminal, the mesh
    reduce tail) goes through here so the cache kind, key components and
    calling convention stay identical by construction: that is what lets
    e.g. the mesh tail share the local verb's compiled entry."""
    import jax

    return ex.cached(
        "block-bucketed",
        graph,
        list(fetch_list),
        list(feed_names),
        lambda: jax.jit(build_masked_reduce(graph, plan, feed_names)),
    )


def dispatch_masked(fn, feeds: Sequence, n: int):
    """Run a masked bucketed program on one block: pad the feeds to the
    ladder and pass the true row count as the traced ``valid`` scalar."""
    feeds, _ = pad_feeds(feeds, n)
    return fn(np.int32(n), *feeds)
