"""The served kernels compile for the real chip at real widths.

The TPU compiler is installed in the sandbox and compiles for a chip
that is DESCRIBED, not attached (a v5e 2x2 host).  Nothing runs, so
this says nothing about results or speed — chip_smoke.py does that on
the chip — but a kernel the chip's compiler would refuse (a slice off
the tiling, too much VMEM, a program that does not fit HBM, a sharding
that cannot be partitioned) fails here, at no chip time.

The topology is described inside a module-scoped fixture, never at
import: only one process may hold the TPU library, and every xdist
worker imports every test file.  All of these tests live in this one
file for the same reason.
"""

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from ceph_tpu.ops import ec_kernels, gf, pallas_ec

K, M = 8, 3
MATRIX = gf.reed_sol_van_matrix(K, M)


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes, dtype=np.uint8):
    args = [jax.ShapeDtypeStruct(s, dtype, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile()


# (B, k, L): one 4 MiB object's stripes, and two coalesced.  The
# (8, k, 1 MiB) shape compiles too but takes ~27 s: run by hand
# (CHANGES.md, PR 23), not kept here.
@pytest.mark.parametrize("shape", [(128, K, 4096), (256, K, 4096)])
def test_pallas_fused_encode_crc(one_chip, shape):
    fn = pallas_ec.make_encode_crc_fn(MATRIX, shape[-1], interpret=False)
    text = _compile(fn, one_chip, shape).as_text()
    # encode + data CRC + parity CRC, each a real Mosaic kernel
    assert text.count("tpu_custom_call") >= 3


def test_pallas_fused_encode_crc_of_a_composed_layered_code(one_chip):
    """What TpuBackend._make_fused serves `technique=lrc` k=4 m=2 l=3
    on a TPU: the byte program with the layers' composed (4 x 4)
    generator, four parity rows over four data chunks, at one 4 MiB
    object's 256 stripes (ISSUE 34)."""
    from ceph_tpu.erasure.registry import registry
    matrix = registry.factory(
        "lrc", {"k": "4", "m": "2", "l": "3"}).coding_matrix
    assert matrix.shape == (4, 4)
    fn = pallas_ec.make_encode_crc_fn(matrix, 4096, interpret=False)
    text = _compile(fn, one_chip, (256, 4, 4096)).as_text()
    assert text.count("tpu_custom_call") >= 3


def test_packet_encode_crc(one_chip):
    """What TpuBackend._make_fused serves a packet-layout codec on a
    TPU: jerasure cauchy_good k=6 m=3 packetsize=32 at one 4 MiB
    object's 171 stripes in their 256-bucket; XORs of whole packets in
    XLA, the data and parity CRC folds the byte program's Mosaic
    kernel."""
    bits = gf.expand_bitmatrix(gf.cauchy_good_matrix(6, 3), 8)
    fn = ec_kernels.make_packet_encode_crc_fn(
        bits, 8, 32, 4096, crc=pallas_ec.make_crc_fn(4096,
                                                     interpret=False))
    compiled = _compile(fn, one_chip, (256, 6, 4096))
    assert compiled.as_text().count("tpu_custom_call") >= 2
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 28


def test_pallas_crc(one_chip):
    fn = pallas_ec.make_crc_fn(4096, interpret=False)
    assert "tpu_custom_call" in _compile(
        fn, one_chip, (1408, 4096)).as_text()


# rows rebuilt by a degraded read: 1..m lost data shards
@pytest.mark.parametrize("n_lost", [1, 2, 3])
def test_xla_decode(one_chip, n_lost):
    """What TpuBackend._fn("bytes") serves: the bit-matrix is an
    operand, so ONE executable covers every decode pattern of a
    shape."""
    fn = ec_kernels._apply_fn()
    g = jax.ShapeDtypeStruct((8 * n_lost, 8 * K), np.uint8,
                             sharding=one_chip)
    data = jax.ShapeDtypeStruct((128, K, 4096), np.uint8,
                                sharding=one_chip)
    compiled = fn.lower(g, data).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


# (rows, bytes): stripe-chunk rows, and whole shard files as deep
# scrub folds them.  The 512 KiB shard file of a 4 MiB object compiles
# too but takes ~36 s: run by hand (CHANGES.md, PR 23), not kept here.
# (S, k, L): one object's resident data stripes, as a read served from
# the HBM cache folds them.
@pytest.mark.parametrize("shape", [(1408, 4096), (64, 64 << 10),
                                   (128, K, 4096)])
def test_xla_scrub_crc(one_chip, shape):
    fn = ec_kernels.make_crc_fn(shape[-1])
    compiled = _compile(fn, one_chip, shape)
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30


# What an S3 size mix runs on a k=4 m=2 pool (PR 42): the fused encode
# at small and odd buckets, the cut of an item out of a coalesced
# batch with its offset an OPERAND (one program a (batch, item) bucket
# pair, whatever the offset), and the check of a cache-served read at
# the item's bucket.
@pytest.mark.parametrize("rows", [1, 2, 32, 64])
def test_size_mix_encode_buckets(one_chip, rows):
    fn = pallas_ec.make_encode_crc_fn(gf.reed_sol_van_matrix(4, 2), 4096,
                                      interpret=False)
    assert "tpu_custom_call" in _compile(
        fn, one_chip, (rows, 4, 4096)).as_text()


@pytest.mark.parametrize("batch,bucket", [(256, 32), (64, 1), (8, 4)])
def test_size_mix_item_slice_takes_its_offset_as_an_operand(
        one_chip, batch, bucket):
    from ceph_tpu.ops import hbm_cache
    args = [jax.ShapeDtypeStruct((batch, n, 4096), np.uint8,
                                 sharding=one_chip) for n in (4, 2)]
    start = jax.ShapeDtypeStruct((), np.int32, sharding=one_chip)
    compiled = hbm_cache._slice_fn(bucket).lower(*args, start).compile()
    text = compiled.as_text()
    assert "dynamic-slice" in text or "dynamic_slice" in text
    assert [tuple(o.shape) for o in compiled.out_info] == \
        [(bucket, 4, 4096), (bucket, 2, 4096)]


@pytest.mark.parametrize("bucket", [1, 32, 64])
def test_size_mix_check_at_the_bucket(one_chip, bucket):
    fn = ec_kernels.make_crc_fn(4096)
    compiled = _compile(fn, one_chip, (bucket, 4, 4096))
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 28
