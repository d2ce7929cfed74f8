"""The aggregation kernels compiled for a v5e that is described, not
attached (the TPU's compiler is installed here; nothing runs): what the
chip's compiler makes of the bucket reduce at the benchmark's widths.

The topology is described inside a fixture and only there, and these
tests live in this one file: a worker loads the TPU's library when it is
given the file, never while a module is imported."""

import re

import jax
import jax.numpy as jnp
import pytest

from pipegcn_tpu.ops.bucket_spmm import ROW_TILE, bucket_aggregate


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
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\S+) ([\w\-]+)\(", line)
        if live and m:
            op = re.search(r'op_name="([^"]*)"', line)
            out.append((m.group(1), m.group(2), m.group(3),
                        op.group(1) if op else ""))
    return out


def _elements(shape: str) -> int:
    dims = re.match(r"\w+\[([\d,]*)\]", shape)
    n = 1
    for d in (dims.group(1).split(",") if dims and dims.group(1) else []):
        n *= int(d)
    return n


# Reddit's remainder at width 256 and Yelp's buckets at width 512, rows
# as the chunking leaves them: (table shapes, source rows, F, dtype)
CASES = {
    "reddit-e4m3": ([(13, 2048), (141, 20000 // 32 * 32), (316, 4000)],
                    233_000, 256, jnp.float8_e4m3fn),
    "reddit-e5m2": ([(13, 2048), (141, 20000 // 32 * 32), (316, 4000)],
                    233_000, 256, jnp.float8_e5m2),
    "reddit-bf16": ([(13, 2048), (141, 20000 // 32 * 32), (316, 4000)],
                    233_000, 256, jnp.bfloat16),
    "yelp-e4m3": ([(9, 150_016), (13, 200_000 // 32 * 32), (19, 90_016)],
                  717_000, 512, jnp.float8_e4m3fn),
    "f32": ([(13, 2048), (141, 20000 // 32 * 32)], 233_000, 64,
            jnp.float32),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_reduce_reads_the_transport_dtype_on_the_chip(one_chip, case):
    """No float32 tensor of message size is an operation's output under
    `rem_reduce` / `rem_gather`, and every chunk's sum is ONE fusion (or
    a bare reduce, for float32) whose operand is the gathered stream in
    its transport dtype: the widening lives inside the reduction."""
    shapes, n_src, f, dt = CASES[case]
    assert all(r % ROW_TILE == 0 for _, r in shapes)
    sds = jax.ShapeDtypeStruct
    fn = jax.jit(lambda x, mats, inv: bucket_aggregate(x, mats, inv,
                                                       scope="rem_"))
    hlo = fn.lower(
        sds((n_src, f), dt, sharding=one_chip),
        [sds(s, jnp.int32, sharding=one_chip) for s in shapes],
        sds((n_src,), jnp.int32, sharding=one_chip)).compile().as_text()
    ops = [o for o in _scheduled(hlo)
           if "rem_reduce" in o[3] or "rem_gather" in o[3]]
    assert ops
    # a chunk's messages, one feature slab wide: [w, rows, slab] elements
    slab = min(f, 256 // jnp.dtype(dt).itemsize)
    messages = {w * min(r, 32 * 1024 * 1024 // (w * slab) // 32 * 32) * slab
                for w, r in shapes}
    wide = [o for o in ops if o[1].startswith("f32[")
            and _elements(o[1]) in messages]
    if dt != jnp.float32:
        assert not wide, wide
    sums = [o for o in ops if "rem_reduce" in o[3]
            and o[2] in ("fusion", "reduce") and "reduce_sum" in o[3]]
    assert len(sums) >= len(shapes)
    if dt != jnp.float32:
        assert all(o[2] == "fusion" and "convert_reduce" in o[0]
                   for o in sums), sums
    # the gathers' outputs stay in the transport dtype
    short = {jnp.float8_e4m3fn: "f8e4m3fn", jnp.float8_e5m2: "f8e5m2",
             jnp.bfloat16: "bf16", jnp.float32: "f32"}[dt]
    gathers = [o for o in ops if o[2] == "fusion" and "rem_gather" in o[3]
               and _elements(o[1]) in messages]
    assert len(gathers) == len(shapes) and all(o[1].startswith(short + "[") for o in gathers)
