"""The aggregation kernels and the training step's dropout compiled for
a v5e that is described, not attached (the TPU's compiler is installed
here; nothing runs): what the chip's compiler makes of the bucket
reduce at the benchmark's widths, and where it draws the dropout masks.

The topology is described inside a fixture and only there, and these
tests live in this one file: a worker loads the TPU's library when it is
given the file, never while a module is imported."""

import re

import jax
import jax.numpy as jnp
import pytest

from pipegcn_tpu.ops.bucket_spmm import (DEFAULT_CHUNK_ELEMS,
                                         GATHER_PART_BYTES, ROW_TILE,
                                         SLAB_BYTES, _rides_as_words,
                                         bucket_aggregate, chunk_rows,
                                         part_bounds, source_parts)


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — no compiler, no test
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    return SingleDeviceSharding(topo.devices[0])


def _scheduled(hlo: str):
    """(name, shape, opcode, scope path) of every instruction the device
    runs as an operation of its own: those of the entry computation and
    of the loop bodies it calls, not those inside a fusion or a
    reduction's region."""
    out, live = [], False
    for line in hlo.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            name = head.group(2)
            live = bool(head.group(1)) or not (
                name.startswith(("fused_computation", "region_"))
                or "scalar_add" in name or "clamp" in name)
            continue
        # a fusion of several outputs has a tuple's shape, "(f32[..], ..)"
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\(",
                     line)
        if live and m:
            op = re.search(r'op_name="([^"]*)"', line)
            out.append((m.group(1), m.group(2), m.group(3),
                        op.group(1) if op else ""))
    return out


_BYTES = {"f8e4m3fn": 1, "f8e5m2": 1, "u8": 1, "s8": 1, "pred": 1,
          "bf16": 2, "f16": 2, "u16": 2, "s16": 2,
          "f32": 4, "u32": 4, "s32": 4}


def _arrays(shape: str):
    """(dtype, bytes an element, elements) of every array in an
    instruction's shape, a tuple's members each."""
    out = []
    for dt, dims in re.findall(r"(\w+)\[([\d,]*)\]", shape):
        n = 1
        for d in (dims.split(",") if dims else []):
            n *= int(d)
        out.append((dt, _BYTES[dt], n))
    return out


# Reddit's remainder at width 256 and Yelp's buckets at width 512, at
# widths fitted to their degree histograms (fit_widths; the narrowest,
# a middle and the widest bucket of each, rows as the benchmark's
# graphs fill them, PERF.md section 6, PR 34), cut into chunks as
# chunk_rows cuts them: (table shapes, source rows, F, dtype). Yelp's
# 716,847 source rows are more than one table under GATHER_PART_BYTES
# holds: its buckets as the builder cuts them (the graph in node
# order), two parts of about 358,424 rows, each with widths fitted to
# its own degrees (a list of table shapes a part)
_REDDIT = [(90, 55_552), (104, 59_008), (538, 416)]
_YELP_PARTS = [[(3, 137_472), (6, 115_168), (19, 2656)],
               [(3, 136_704), (7, 89_408), (19, 6784)]]
CASES = {
    "reddit-e4m3": (_REDDIT, 233_000, 256, jnp.float8_e4m3fn),
    "reddit-e5m2": (_REDDIT, 233_000, 256, jnp.float8_e5m2),
    "reddit-bf16": (_REDDIT, 233_000, 256, jnp.bfloat16),
    "yelp-e4m3": ([(7, 92_960), (11, 89_696), (32, 2528)],
                  717_000, 512, jnp.float8_e4m3fn),
    "yelp-e4m3-parts": (_YELP_PARTS, 716_847, 512, jnp.float8_e4m3fn),
    "f32": ([(90, 2048), (104, 20000 // 32 * 32)], 233_000, 64,
            jnp.float32),
}


def _compiled(one_chip, case, scope=""):
    """The compiled text of bucket_aggregate over CASES[case]'s tables
    (one list of them, or a list a part and a permutation a part), as
    its callers use it: the result divided by the destination rows'
    degrees (make_bucket_spmm_fn's and the block kernel's `scale`)."""
    shapes, n_src, f, dt = CASES[case]
    sds = jax.ShapeDtypeStruct

    def idx(s):
        return sds(s, jnp.int32, sharding=one_chip)

    inv = sds((n_src,), jnp.int32, sharding=one_chip)
    if isinstance(shapes[0], list):
        mats, invs = [[idx(s) for s in p] for p in shapes], \
            [inv] * len(shapes)
    else:
        mats, invs = [idx(s) for s in shapes], inv
    fn = jax.jit(lambda x, mats, inv, deg: bucket_aggregate(
        x, mats, inv, scope=scope) / deg[:, None])
    return fn.lower(sds((n_src, f), dt, sharding=one_chip), mats, invs,
                    sds((n_src,), jnp.float32, sharding=one_chip)
                    ).compile().as_text()


@pytest.mark.parametrize("case", sorted(CASES))
def test_reduce_reads_the_transport_dtype_on_the_chip(one_chip, case):
    """Under `rem_gather` / `rem_reduce` no operation writes a chunk's
    messages in anything wider than they were gathered in, and every
    chunk's sum is ONE fusion (or a bare reduce, for float32) whose
    operand is the gathered stream itself: the widening lives inside the
    reduction. fp8 rows are gathered as 16-bit words of 128 columns
    (half the elements, the same bytes), split and widened a byte plane
    at a time inside that fusion, whose output is two [rows, F/2]
    halves. A direction cut into parts holds to all of it part by
    part, and packs each part's table once a call."""
    hlo = _compiled(one_chip, case, "rem_")
    shapes, n_src, f, dt = CASES[case]
    parts = shapes if isinstance(shapes[0], list) else [shapes]
    shapes = [s for p in parts for s in p]
    assert all(r % ROW_TILE == 0 for _, r in shapes)
    ops = [o for o in _scheduled(hlo)
           if "rem_reduce" in o[3] or "rem_gather" in o[3]]
    assert ops
    # a chunk's messages, one feature slab wide: [w, rows, slab] elements
    item = jnp.dtype(dt).itemsize
    slab = min(f, 256 // item)
    words = _rides_as_words(dt, slab)
    assert words == (item == 1)
    chunks = [(w, chunk_rows(w, r, slab, DEFAULT_CHUNK_ELEMS)[0])
              for w, r in shapes]
    assert all(c % ROW_TILE == 0 and c <= r for (_, c), (_, r)
               in zip(chunks, shapes))
    messages = {w * r * slab for w, r in chunks}
    # per element a message is `item` bytes; as words, half as many
    # elements of two bytes. Anything wider of either count is a copy
    wide = [o for o in ops for _, size, n in _arrays(o[1])
            if (n in messages and size > item)
            or (words and 2 * n in messages and size > 2)]
    if dt != jnp.float32:
        assert not wide, wide
    sums = [o for o in ops if "rem_reduce" in o[3]
            and o[2] in ("fusion", "reduce") and "reduce_sum" in o[3]]
    assert len(sums) >= len(shapes)
    if dt != jnp.float32:
        assert all(o[2] == "fusion" and "convert_reduce" in o[0]
                   for o in sums), sums
    # the gathers' outputs: the transport dtype, or its words
    short = "u16" if words else {jnp.bfloat16: "bf16",
                                 jnp.float32: "f32"}[dt]
    stream = {n // 2 for n in messages} if words else messages
    gathers = [o for o in ops if o[2] == "fusion" and "rem_gather" in o[3]
               and any(n in stream for _, _, n in _arrays(o[1]))]
    assert len(gathers) == len(shapes), gathers
    for o in gathers:
        (got, _, n), = _arrays(o[1])
        assert got == short, o
        if words:
            assert o[1].startswith(f"u16[{n // 128},128]"), o
    if words:
        # a chunk's sums are two [rows, F/2] halves, one a byte plane
        # (one fusion of two outputs, or two that read the same words)
        halves = [a for o in sums for a in _arrays(o[1])]
        assert len(halves) >= 2 * len(shapes), sums
        assert all(got == "f32" and n in {r * slab // 2 for _, r in chunks}
                   for got, _, n in halves), sums
        # and each part's table is packed in one pass a call, not once
        # a chunk
        tables = {(hi - lo + 1) * slab // 2
                  for lo, hi in part_bounds(n_src, len(parts))}
        packs = [o for o in ops if "rem_gather" in o[3]
                 and any(got == "u16" and n in tables
                         for got, _, n in _arrays(o[1]))]
        assert len(packs) == len(parts), packs


@pytest.mark.parametrize("case", ["yelp-e4m3-parts", "reddit-e4m3"])
def test_every_gather_reads_a_table_in_s1(one_chip, case):
    """No gather reads a table taller than GATHER_PART_BYTES: Yelp's
    716,847 source rows are cut into two parts (Reddit's 233,000 stay
    one), every gather under `gather` reads a word table of at most
    that many rows, and the chip's compiler keeps each in memory space
    S(1) (a part's table is packed once the part before it is summed;
    interleaved, a part's chunk loops read it from HBM). The parts meet
    in the unpermute: one take a part, and their sum is no pass of its
    own (it rides in the fusion that consumes the result)."""
    shapes, n_src, f, dt = CASES[case]
    k = len(shapes) if isinstance(shapes[0], list) else 1
    assert source_parts(n_src) == k
    rows = GATHER_PART_BYTES // SLAB_BYTES
    hlo = _compiled(one_chip, case)
    ops = _scheduled(hlo)
    shape = {}
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) ", line)
        if m:
            shape[m.group(1)] = m.group(2)
    operands = _operands(hlo)
    gathers = [o for o in ops if o[2] == "fusion" and o[1].startswith("u16[")
               and re.search(r"(^|/)gather/.*gather$", o[3])]
    assert len(gathers) >= len([s for p in (shapes if k > 1 else [shapes])
                                for s in p])
    for o in gathers:
        table = shape[operands[o[0]][0]]
        m = re.match(r"u16\[(\d+),128\]", table)
        assert m and int(m.group(1)) <= rows, (o, table)
        assert "S(1)" in table, (o, table)
    takes = [o for o in ops if re.search(r"(^|/)unpermute/", o[3])
             and o[2] == "fusion" and o[1].startswith(f"f32[{n_src},")]
    assert len(takes) == k, takes
    assert not [o for o in ops if re.search(r"(^|/)unpermute/add", o[3])
                and o[1].startswith("f32")]


@pytest.mark.parametrize("case", ["yelp-e4m3-parts", "reddit-e4m3"])
def test_each_row_sum_is_written_once(one_chip, case):
    """Yelp's two parts of two slabs and Reddit's one of one: under the
    aggregation's scopes every destination row's float32 sum is written
    once, in place, into one buffer of every part's sums, which is
    allocated and never filled (only each part's zero row is written
    as such), and each part's take writes [n_out, F], the result's own
    layout: no zero fill of a bucket's sums, no stacked [S, n_out,
    slab] slab output, no copy, transpose, pad or concatenation of the
    result, and no other f32 array of n_out rows is written (one take a
    part, at most one a slab). What the counter `result_bytes` says is
    what the program allocates for its sums and writes in its takes."""
    from pipegcn_tpu.ops.bucket_spmm import part_heights, result_bytes

    shapes, n_out, f, dt = CASES[case]
    parts = shapes if isinstance(shapes[0], list) else [shapes]
    k, slab = len(parts), SLAB_BYTES // jnp.dtype(dt).itemsize
    n_s = -(-f // slab)
    hlo = _compiled(one_chip, case)
    plumbing = ("parameter", "tuple", "get-tuple-element", "bitcast",
                "while", "copy-start", "copy-done")
    ops = [o for o in _scheduled(hlo) if o[2] not in plumbing
           and re.search(r"(^|/)(gather|reduce|unpermute|relayout)(/|$)",
                         o[3])]
    assert ops
    f32 = [(o, n) for o in ops for dt_, _, n in _arrays(o[1])
           if dt_ == "f32"]
    # zero fills: only the parts' zero rows, one row of F each (or
    # fused into their write)
    fills = [(o, n) for o, n in f32
             if o[2] == "broadcast" or o[0].startswith("broadcast")]
    assert all(n == f for _, n in fills), fills
    # the buffer of sums: allocated once, [sum of the parts' heights,
    # F] (scheduled alone, or inside the fusion that writes the first
    # zero row)
    mats = [[jax.ShapeDtypeStruct(shape, jnp.int32) for shape in p]
            for p in parts]
    heights = part_heights(mats)
    buffer = f"f32[{sum(heights)},{f}]"
    allocs = [line for line in hlo.splitlines()
              if 'custom_call_target="AllocateBuffer"' in line]
    assert len(allocs) == 1 and f"= {buffer}" in allocs[0], allocs
    alloc = [o for o in ops if o[2] == "custom-call"]
    assert all(o[1].startswith(buffer) for o in alloc), alloc
    # nothing of the result is stacked by slab, moved or laid out again
    big = n_out * slab
    assert not [o for o, n in f32 if n >= big and (
        o[2] in ("copy", "transpose", "pad", "concatenate")
        or re.search(r"copy|transpose|pad|concatenate", o[0]))]
    assert not [o for o in ops if re.match(rf"f32\[\d+,{n_out},", o[1])]
    # the writes of n_out rows: one take a part, [n_out, F], no more
    # than one a slab; the in-place writes into the buffer are its size
    rows = [o for o, n in f32 if n >= big and o[2] != "dynamic-update-slice"
            and o not in alloc]
    assert len(rows) == k <= n_s, rows
    assert all(o[1].startswith(f"f32[{n_out},{f}]") and o[2] == "fusion"
               and re.search(r"(^|/)unpermute/", o[3]) for o in rows), rows
    written = [o for o, n in f32 if o[2] == "dynamic-update-slice"
               or "dynamic-update-slice" in o[0]]
    assert written and all(o[1].startswith(buffer) for o in written), \
        written
    # and every other f32 write is a chunk's sums, written by its reduce
    others = [o for o, n in f32 if o not in alloc + rows + written
              and (o, n) not in fills]
    assert others and all(o[2] == "fusion" and "reduce" in o[0]
                          and n < big for o, n in f32 if o in others), others
    # the counter reads what the kernel allocates and takes, off the
    # direction's tables as the builder stacks them (stack_direction)
    tables = {}
    for p, part in enumerate(mats):
        stem = "bkt_fwd" if p == 0 else f"bkt_fwd_p{p}"
        tables[f"{stem}_inv"] = jax.ShapeDtypeStruct((n_out,), jnp.int32)
        tables.update({f"{stem}_{b:02d}": m for b, m in enumerate(part)})
    made = 4 * (sum(heights) * f + sum(n for o, n in f32 if o in rows))
    assert result_bytes(tables, "bkt_fwd", f) == made


# The dense classes of the Reddit cells (PERF.md section 5, PR 37: the
# traced class shapes), as the builder stores them: (leading axes, K).
# Group 1 (`block-f8`): four scanned classes and a whole one; groups
# of 4 (`reddit_p1_block`): five scanned and a whole one. The
# backward's classes have the forward's shapes (the graph is symmetric
# in its statistics): the SAME contraction over the transposes' copy.
_DENSE = {
    1: [((36, 8), 63), ((38, 12), 42), ((10, 5), 94), ((7, 18), 28),
        ((47,), 3)],
    4: [((106, 1), 94), ((38, 2), 63), ((21, 1), 141), ((6, 1), 211),
        ((5, 4), 28), ((3,), 42)],
}


def _operands(hlo: str):
    """{instruction: its operands' names} over the whole module."""
    out = {}
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (?:\(.*?\)|\S+) "
                     r"[\w\-]+\((.*?)\)(?:,|$)", line)
        if m:
            out[m.group(1)] = re.findall(r"%([\w.\-]+)", m.group(2))
    return out


@pytest.mark.parametrize("group", [1, 4])
def test_stored_a_is_read_where_it_lies(one_chip, group):
    """The block kernel's dense half at the Reddit cells' class shapes,
    forward and backward in one program: under `unpack` the device
    schedules no gather, copy, pad or concatenate (a chunk's A is a
    slice of the scan's xs, already in the layout the einsum's fusion
    reads: the contraction packed along the second-minor axis, the
    output rows on the lanes), and no instruction writes anything of a
    class's whole A: the table is touched by the loop that carries it
    and the slice that reads it, nothing else. The einsum's fusion
    unpacks the slice itself and writes its rows into the direction's
    one result buffer."""
    from pipegcn_tpu.ops.block_spmm import make_block_spmm_fn

    tile, f, n_rows = 256, 256, 232_965
    n_tiles = -(-n_rows // tile)
    sds = jax.ShapeDtypeStruct
    tabs = {}
    for d in ("fwd", "bwd"):
        tabs[f"blk_{d}_inv"] = sds((n_tiles,), jnp.int32, sharding=one_chip)
        tabs[f"blkrem_{d}_inv"] = sds((n_rows,), jnp.int32,
                                      sharding=one_chip)
        tabs[f"blkrem_{d}_00"] = sds((8, 64), jnp.int32, sharding=one_chip)
        for w, (lead, k) in enumerate(_DENSE[group]):
            tabs[f"blk_{d}_g{w:02d}a"] = sds(
                lead + (k, tile // 8, group * tile), jnp.uint8,
                sharding=one_chip)
            tabs[f"blk_{d}_g{w:02d}t"] = sds(lead + (k,), jnp.int32,
                                             sharding=one_chip)

    def both(tabs, deg, x, g):
        fn = make_block_spmm_fn(tabs, deg, n_rows, n_rows, tile,
                                rem_dtype="float8")
        out, vjp = jax.vjp(fn, x)
        return out, vjp(g)[0]

    hlo = jax.jit(both).lower(
        tabs, sds((n_rows,), jnp.float32, sharding=one_chip),
        sds((n_rows, f), jnp.bfloat16, sharding=one_chip),
        sds((n_rows, f), jnp.float32, sharding=one_chip)).compile().as_text()
    ops = _scheduled(hlo)
    unpack = [o for o in ops if re.search(r"/unpack/", o[3])]
    assert {"bwd" in o[3] for o in unpack} == {False, True}
    moved = [o for o in unpack
             if o[2] in ("gather", "copy", "pad", "concatenate")
             or re.search(r"gather|copy|pad|concatenate", o[0])]
    assert not moved, moved
    # a scanned class's whole A: written by nothing, read by the loop
    # that carries it (and the tuple plumbing around it) and by the
    # fusion that slices a chunk off it. (A class stored whole is the
    # einsum's operand as it lies; where the compiler prefetches a
    # small one to its fast memory, that is its placement, not a pass
    # the program asked for.)
    scanned = [(lead, k) for lead, k in _DENSE[group] if len(lead) > 1]
    whole = {lead[0] * lead[1] * k * tile // 8 * group * tile
             for lead, k in scanned}
    plumbing = ("parameter", "while", "tuple", "get-tuple-element",
                "bitcast")
    shapes = {o[0]: o[1] for o in ops}
    writes = [o for o in ops if o[2] not in plumbing
              and any(dt == "u8" and n in whole
                      for dt, _, n in _arrays(o[1]))]
    assert not writes, writes
    operands = _operands(hlo)
    reads = [o for o in ops if o[2] not in plumbing
             and any(dt == "u8" and n in whole
                     for src in operands.get(o[0], ())
                     for dt, _, n in _arrays(shapes.get(src, "")))]
    chunk = {lead[1] * k * tile // 8 * group * tile for lead, k in scanned}
    assert len(reads) == 2 * len(scanned), reads
    for o in reads:
        (dt, _, n), = _arrays(o[1])
        assert o[2] == "fusion" and dt == "u8" and n in chunk, o
    # and the einsum unpacks the packed chunk inside its own fusion:
    # what reads a slice writes the class's f32 result, nothing else
    slices = {o[0] for o in reads}
    users = [o for o in ops
             if slices & set(operands.get(o[0], ())) and o[2] != "bitcast"]
    assert len(users) == len(slices), users
    assert all(o[2] == "fusion" and _arrays(o[1])[0][0] == "f32"
               and "rksm,rksf->rmf" in o[3] for o in users), users
    # ... and writes it in place into the ONE result buffer of the
    # direction (every class's rows and a sentinel): no class has a
    # result of its own for the compiler to keep in fast memory, where
    # it crowded out the operand's take (PERF.md section 6, PR 37)
    n_rows = sum(lead[0] * (lead[1] if len(lead) > 1 else 1)
                 for lead, _ in _DENSE[group])
    for o in users:
        assert _arrays(o[1]) == [("f32", 4,
                                  (n_rows + 1) * group * tile * f)], o
    own = {lead[0] * lead[1] * group * tile * f for lead, _ in scanned}
    assert not [o for o in ops if o[2] != "parameter"
                for dt, _, n in _arrays(o[1]) if dt == "f32" and n in own]


def _fused_opcodes(hlo: str):
    """{computation: the opcodes of its instructions and of every
    computation it calls, nested} over the whole module."""
    body, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            cur = head.group(1)
            body[cur] = []
        elif cur is not None:
            body[cur].append(line)
    memo = {}

    def ops(name):
        if name not in memo:
            memo[name] = set()
            for line in body.get(name, ()):
                m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (?:\(.*?\)|\S+) "
                             r"([\w\-]+)\(", line)
                if m:
                    memo[name].add(m.group(1))
                for sub in re.findall(r"(?:calls|to_apply)=%([\w.\-]+)",
                                      line):
                    memo[name] |= ops(sub)
        return memo[name]

    return {name: ops(name) for name in body}


def test_dropout_masks_drawn_once(one_chip):
    """Yelp's training forward and backward (use_pp, 600 -> 512 -> 512
    -> 512 -> 100 with a dense tail of two, dropout 0.1, bf16, 716,848
    rows; an fp8 cast behind a barrier stands in for the aggregation,
    whose transport cast reads the dropped input first): no scheduled
    fusion outside `dropout` carries threefry's rounds (a shift-right-
    logical with an xor in its fused computation), and each dropout
    layer's mask is written by one fusion, once a step. Left to fuse,
    the compiler re-derives a mask in every matmul that reads it,
    forward, dW and dX, and in the cast (docs/PERF_NOTES.md)."""
    from pipegcn_tpu.models.sage import ModelConfig, forward, init_params
    from pipegcn_tpu.train.losses import bce_logits_sum

    n = 716_848
    cfg = ModelConfig(layer_sizes=(300, 512, 512, 512, 100), n_linear=2,
                      use_pp=True, dropout=0.1, dtype="bfloat16",
                      train_size=n)
    sds = jax.ShapeDtypeStruct

    def spmm(h):
        q = jnp.clip(h.astype(jnp.float32), -448, 448).astype(
            jnp.float8_e4m3fn)
        return jax.lax.optimization_barrier(q).astype(jnp.float32)

    def step(params, feat, label, mask, deg, rng):
        def loss(p):
            logits, _ = forward(p, cfg, feat, None, None, deg, n,
                                training=True, rng=rng,
                                comm_update=lambda i, h: h, spmm_fn=spmm)
            return bce_logits_sum(logits, label, mask)
        return jax.value_and_grad(loss)(params)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))
    hlo = jax.jit(step).lower(
        params, sds((n, 600), jnp.bfloat16, sharding=one_chip),
        sds((n, 100), jnp.float32, sharding=one_chip),
        sds((n,), jnp.bool_, sharding=one_chip),
        sds((n,), jnp.float32, sharding=one_chip),
        jax.random.PRNGKey(0)).compile().as_text()
    opcodes = _fused_opcodes(hlo)
    calls = {}
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = .* calls=%([\w.\-]+)",
                     line)
        if m:
            calls[m.group(1)] = m.group(2)
    rounds = [o for o in _scheduled(hlo) if o[2] == "fusion"
              and {"shift-right-logical", "xor"}
              <= opcodes.get(calls.get(o[0]), set())]
    assert rounds
    assert not [o for o in rounds if "/dropout/" not in o[3]], rounds
    # the fusions that write a mask-sized array (layer 0's dropped
    # features, the other layers' stored masks): one a layer at most,
    # and each layer's mask written once
    sized = {n * 600, n * 512}
    draws = [o for o in rounds
             if any(k in sized for _, _, k in _arrays(o[1]))]
    layers = [re.search(r"layer(\d+)\)?/dropout/", o[3]).group(1)
              for o in draws]
    assert len(layers) == len(set(layers)), draws
    written = [k for o in draws for _, _, k in _arrays(o[1]) if k in sized]
    assert len(written) == cfg.n_layers, draws
