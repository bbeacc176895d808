"""Compile the dedup kernels for a described TPU v5e, without a chip.

The TPU compiler is installed with JAX and compiles for a topology that
is described rather than attached, so these tests catch what interpret
mode cannot: block shapes the Mosaic lowering refuses, layouts that do
not match XLA's, primitives with no TPU lowering.  Nothing runs; each
test lowers at real widths and asserts that the compiled program holds
the Pallas kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU compiler library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

M = 100  # the paper's signature width


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache, so keep these compiles out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def _assert_kernel(fn, *args, count=1):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") >= count


@pytest.mark.parametrize("width", [256, 1024])
def test_fused_ingest_compiles(spec, width):
    from repro.kernels.fused_ingest import fused_ingest

    _assert_kernel(
        lambda t, ln, s: fused_ingest(t, ln, s, interpret=False),
        spec((256, width), jnp.uint32), spec((256,), jnp.int32),
        spec((M,), jnp.uint32))


# (n, M, r): the paper's widths, and FineWeb's MinHash settings (word
# 5-grams, 112 hashes in 14 bands of 8), whose M is not a multiple of
# the 128 lanes.
WIDTHS = [pytest.param(8, M, 2, id="paper"),
          pytest.param(5, 112, 8, id="fineweb")]


@pytest.mark.parametrize("n,m,r", WIDTHS)
def test_bytes_to_bands_compiles(spec, n, m, r):
    from repro.kernels.byte_shingle import bytes_to_bands

    # Two kernels: the byte tokenizer and the fused ingest it feeds.
    _assert_kernel(
        lambda d, ln, s: bytes_to_bands(d, ln, s, n=n, r=r,
                                        interpret=False),
        spec((256, 2048), jnp.uint8), spec((256,), jnp.int32),
        spec((m,), jnp.uint32), count=2)


@pytest.mark.parametrize("entry,m,store,pairs", [
    pytest.param("pair_counts", M, None, 4096, id="pair_counts"),
    pytest.param("indexed_pair_counts", M, (8192, M), 4096,
                 id="indexed_pair_counts"),
    pytest.param("masked_pair_counts", M, None, 4096,
                 id="masked_pair_counts"),
    pytest.param("masked_indexed_pair_counts", M, (8192, M), 4096,
                 id="masked_indexed_pair_counts"),
    # The largest verify batch over the admission_bytes cell's device
    # signature store at FineWeb's widths: 720,896 rows of 112 hashes,
    # padded to 128 lanes.
    pytest.param("indexed_pair_counts", 112, (720_896, 128), 8192,
                 id="indexed_pair_counts-fineweb")])
def test_sigjaccard_compiles(spec, entry, m, store, pairs):
    from repro.kernels import sigjaccard

    fn = getattr(sigjaccard, entry)
    P = pairs
    rows = spec((P, m), jnp.uint32)
    idx = spec((P,), jnp.int32)
    valid = spec((P,), jnp.bool_)
    kw = {"width": m} if store and store[1] != m else {}
    store = spec(store or (0, m), jnp.uint32)
    args = {
        "pair_counts": (rows, rows),
        "indexed_pair_counts": (store, idx, idx),
        "masked_pair_counts": (rows, rows, valid),
        "masked_indexed_pair_counts": (store, idx, idx, valid),
    }[entry]
    _assert_kernel(lambda *a: fn(*a, interpret=False, **kw), *args)


@pytest.mark.parametrize("stage", ["ngram", "minhash", "bandfold"])
def test_staged_kernels_compile(spec, stage):
    from repro.kernels import bandfold, minhash, ngram

    D, L = 256, 256
    if stage == "ngram":
        _assert_kernel(
            lambda t, ln: ngram.ngram_hashes(t, ln, interpret=False),
            spec((D, L), jnp.uint32), spec((D,), jnp.int32))
    elif stage == "minhash":
        _assert_kernel(
            lambda g, v, s: minhash.minhash_signatures(
                g, v, s, interpret=False),
            spec((D, L), jnp.uint32), spec((D, L), jnp.bool_),
            spec((M,), jnp.uint32))
    else:
        _assert_kernel(
            lambda s: bandfold.band_values(s, 2, interpret=False),
            spec((D, M), jnp.uint32))


def test_sharded_device_stage2_compiles(topo, monkeypatch):
    """The stage2="device" sharded step on the described 4-chip mesh:
    fused ingest, the all_to_all shuffle and both sigjaccard kernels
    under shard_map."""
    from repro.core import dist_lsh
    from repro.kernels import common

    # The kernels pick interpret mode from the default backend, which
    # is the CPU here; steer it so they lower for the TPU.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert common.resolve_interpret(None) is False
    mesh = dist_lsh.docs_mesh(topo.devices)
    cfg = dist_lsh.DistLSHConfig(stage2="device", fused_ingest=True)
    step = dist_lsh.make_streamed_dedup_step(cfg, mesh)

    def run(tokens, lengths, seeds, offsets):
        out = step(tokens, lengths, seeds, offsets)
        return out["sig"], [(g["edges"], g["device_match_counts"],
                             g["device_covered"]) for g in out["groups"]]

    rows = NamedSharding(mesh, PartitionSpec("docs"))
    every = NamedSharding(mesh, PartitionSpec())
    D = 4096
    try:
        text = jax.jit(run).lower(
            jax.ShapeDtypeStruct((D, 256), jnp.uint32, sharding=rows),
            jax.ShapeDtypeStruct((D,), jnp.int32, sharding=rows),
            jax.ShapeDtypeStruct((M,), jnp.uint32, sharding=every),
            jax.ShapeDtypeStruct((4,), jnp.uint32, sharding=rows),
        ).compile().as_text()
    finally:
        # Traces made under the steered backend must not serve a later
        # CPU caller of the same shapes.
        jax.clear_caches()
    # fused_ingest + masked_indexed_pair_counts + masked_pair_counts.
    assert text.count("tpu_custom_call") >= 3
    assert "all-to-all" in text
