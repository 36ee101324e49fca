"""Ahead-of-time compiles of the compiled wavefront for a described v5e chip.

No chip is needed: the TPU compiler compiles ``ltsp_dp_tables(...,
interpret=False)`` against a described ``v5e:2x2`` topology and refuses what
the chip would refuse (illegal block shapes, unaligned dynamic slices, scoped
VMEM overflow).  The cases are the buckets ``chip_smoke.py`` runs: the paper
median bucket, the small-tape buckets with B > 1, the LOGDP span and SIMPLEDP
disjoint variants, and the band in 16-row chunks; the paper's tail bucket
(256, 8192), which the chip benchmark runs, exact and under a LOGDP span;
R = 128, whose bands are chunked like the large buckets'; and the bucket
(512, 8192), whose two tables take 12 GiB of the chip's 16 GB.  At the
cells' buckets the kernel writes each diagonal into the tables in place, so
the optimised program holds no scatter and no copy of a table.  Each program
must fit the chip's 16 GB of HBM, and the kernel's scoped VMEM what the
wavefront asks for (``_vmem_limit_bytes``).  The device traceback that walks
the 16-bit argmin plane compiles at the median bucket and at (512, 8192)
too, holding no copy of it.

The topology is described inside a module fixture, never at import time, so
every test worker collects the same tests and only the worker that runs this
file loads the TPU compiler.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.ltsp_dp.ltsp_dp import (
    _DEFAULT_SCOPED_VMEM,
    ARGMIN_DTYPE,
    DEFAULT_CAND_TILE,
    _vmem_limit_bytes,
    chunk_rows,
    ltsp_dp_tables,
)
from repro.kernels.ltsp_dp.walk import traceback_device

#: HBM of one TPU v5e chip (Google Cloud documentation, "TPU v5e").
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the persistent
    # cache without one; keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize(
    "B, R, S, kw",
    [
        pytest.param(1, 256, 4096, {}, id="paper-median-B1-R256-S4096"),
        pytest.param(4, 8, 128, {}, id="serving-B4-R8-S128"),
        pytest.param(2, 64, 2048, {}, id="small-B2-R64-S2048"),
        pytest.param(2, 64, 2048, {"cand_tile": 16}, id="banded-tile16-B2-R64"),
        pytest.param(2, 32, 1024, {"span": 5}, id="logdp-span5-B2-R32"),
        pytest.param(2, 64, 1024, {"disjoint": True}, id="simpledp-disjoint-B2-R64"),
        pytest.param(1, 256, 4096, {"span": 40}, id="logdp-span40-B1-R256"),
        pytest.param(1, 256, 8192, {}, id="paper-tail-B1-R256-S8192"),
        pytest.param(1, 256, 8192, {"span": 5}, id="logdp-span5-B1-R256-S8192"),
        pytest.param(1, 128, 4096, {}, id="banded-B1-R128-S4096"),
        pytest.param(1, 512, 8192, {}, id="r512-B1-R512-S8192"),
    ],
)
def test_wavefront_compiles_for_v5e(one_chip, B, R, S, kw):
    def spec(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    args = [spec((B, R))] * 4 + [spec((B,))]
    compiled = ltsp_dp_tables.lower(*args, S=S, interpret=False, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    used = (
        ma.argument_size_in_bytes
        + ma.output_size_in_bytes
        + ma.temp_size_in_bytes
        - ma.alias_size_in_bytes
    )
    # the outputs alone are T and C, B*R*R*S words of int32 and int16
    assert ma.output_size_in_bytes >= B * R * R * S * (4 + 2)
    assert used < V5E_HBM_BYTES, f"{used / 2**30:.2f} GiB does not fit one v5e"
    H = chunk_rows(R, kw.get("span"), kw.get("cand_tile", DEFAULT_CAND_TILE))
    limit = _vmem_limit_bytes(S, 4, H) or _DEFAULT_SCOPED_VMEM
    vmem = _kernel_scoped_vmem(compiled.as_text())
    assert 0 < vmem <= limit, (vmem, limit)


@pytest.mark.parametrize(
    "B, R, S, kw",
    [
        pytest.param(1, 256, 4096, {}, id="paper-median-B1-R256-S4096"),
        pytest.param(1, 256, 4096, {"span": 5}, id="logdp-span5-B1-R256-S4096"),
        pytest.param(1, 256, 8192, {}, id="paper-tail-B1-R256-S8192"),
        pytest.param(1, 512, 8192, {}, id="r512-B1-R512-S8192"),
    ],
)
def test_wavefront_writes_each_diagonal_in_place(one_chip, B, R, S, kw):
    """The kernel's output blocks are the tables themselves, and the columns
    are read from ``T``: the optimised program holds no scatter and no copy
    of a table, its temporaries are a few MiB, with no workspace table
    beside ``T`` and ``C``, and all it holds is within 13 GiB at the bucket
    (512, 8192)."""
    def spec(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    args = [spec((B, R))] * 4 + [spec((B,))]
    compiled = ltsp_dp_tables.lower(*args, S=S, interpret=False, **kw).compile()
    hlo = compiled.as_text()
    assert not re.search(r"\bscatter\(", hlo)
    tables = [f"s32[{B},{R},{R},{S // 128},128]", f"s16[{B},{R},{R},{S // 128},128]"]
    copies = [ln for ln in hlo.splitlines() if re.search(r"\bcopy(-start)?\(", ln)]
    results = [ln.split("=", 1)[1].split("copy", 1)[0] for ln in copies]
    assert not [r for r in results if any(t in r for t in tables)], copies
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < 4 << 20, ma.temp_size_in_bytes
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert used <= 13 * 2**30, used


def _kernel_scoped_vmem(hlo: str) -> int:
    """Scoped VMEM (memory space 1) the compiled wavefront uses, from its
    custom call's backend config."""
    [line] = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    configs = re.search(r'"used_scoped_memory_configs":\[(.*?)\]', line).group(1)
    return sum(int(size) for size in re.findall(
        r'"memory_space":"1","offset":"\d+","size":"(\d+)"', configs))


@pytest.mark.parametrize("B, R, S", [(1, 256, 4096), (1, 512, 8192)],
                         ids=["paper-median-B1-R256-S4096", "r512-B1-R512-S8192"])
def test_device_walk_compiles_for_v5e_without_a_copy_of_the_plane(one_chip, B, R, S):
    shape = (B, R, R, S // 128, 128)
    T = jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    C = jax.ShapeDtypeStruct(shape, ARGMIN_DTYPE, sharding=one_chip)
    x = jax.ShapeDtypeStruct((B, R), jnp.int32, sharding=one_chip)
    compiled = traceback_device.lower(T, C, x).compile()
    ma = compiled.memory_analysis()
    plane_bytes = B * R * R * S * 2  # C: 512 MiB at the median bucket, 4 GiB at R = 512
    assert ma.argument_size_in_bytes >= 3 * plane_bytes
    copies = [ln for ln in compiled.as_text().splitlines()
              if re.search(r"\bcopy(-start)?\(", ln) and "s16[" in ln]
    assert not copies, copies
    # detours [B, R, 2] and three [B] vectors: what crosses to the host
    assert ma.output_size_in_bytes < 2**16
    # the walk reads T and C in place: its temporaries are a few stack
    # frames and scalars, not a plane
    assert ma.temp_size_in_bytes < plane_bytes // 256, ma.temp_size_in_bytes
