"""Native layer tests: C++ LZ4 codec, zstd binding, host arena, and
compressed shuffle/spill round trips."""

import ctypes
import os

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.native import get_lib, build_error
from spark_rapids_tpu.native import codec as ncodec
from spark_rapids_tpu.native.arena import HostArena


def test_native_lib_builds():
    lib = get_lib()
    assert lib is not None, f"native build failed: {build_error()}"


@pytest.mark.parametrize("payload", [
    b"",
    b"a",
    b"hello world " * 1000,
    bytes(range(256)) * 64,
    np.random.default_rng(0).integers(0, 255, 100_000,
                                      dtype=np.uint8).tobytes(),
    b"\x00" * 65536,
])
def test_lz4_roundtrip(payload):
    comp = ncodec.lz4_compress(payload)
    assert ncodec.lz4_decompress(comp) == payload


def test_lz4_compresses_repetitive_data():
    data = b"abcdefgh" * 10_000
    comp = ncodec.lz4_compress(data)
    assert len(comp) < len(data) // 10


def test_lz4_interops_with_system_liblz4():
    """Our block output must decode with the canonical liblz4."""
    import ctypes.util
    name = ctypes.util.find_library("lz4") or "liblz4.so.1"
    try:
        syslz4 = ctypes.CDLL(name)
    except OSError:
        pytest.skip("no system liblz4")
    syslz4.LZ4_decompress_safe.restype = ctypes.c_int
    syslz4.LZ4_decompress_safe.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                                           ctypes.c_int, ctypes.c_int]
    data = (b"the quick brown fox jumps over the lazy dog. " * 500 +
            os.urandom(1000))
    framed = ncodec.lz4_compress(data)
    n, backend = ncodec._FRAME.unpack_from(framed, 0)
    if backend != ncodec._B_NATIVE_LZ4:
        pytest.skip("native codec unavailable")
    block = framed[ncodec._FRAME.size:]
    out = ctypes.create_string_buffer(n)
    m = syslz4.LZ4_decompress_safe(block, out, len(block), n)
    assert m == n and out.raw[:n] == data


def test_zstd_roundtrip():
    data = b"columnar data! " * 5000
    comp = ncodec.zstd_compress(data)
    assert ncodec.zstd_decompress(comp) == data
    assert len(comp) < len(data)


def test_lz4_rejects_truncated_input():
    comp = ncodec.lz4_compress(b"some compressible data " * 100)
    with pytest.raises(Exception):
        ncodec.lz4_decompress(comp[:-5])


def test_arena_alloc_reset():
    a = HostArena(1 << 20)
    v1 = a.alloc(1000)
    v2 = a.alloc(3000, align=256)
    assert v1 is not None and v2 is not None
    v1[:4] = b"abcd"
    v2[:4] = b"efgh"
    assert bytes(v1[:4]) == b"abcd" and bytes(v2[:4]) == b"efgh"
    assert a.used >= 4000
    assert a.n_allocs == 2
    big = a.alloc(2 << 20)
    assert big is None  # exhausted, no exception
    a.reset()
    assert a.used == 0
    v3 = a.alloc(64)
    assert v3 is not None
    a.close()


def test_compressed_batch_roundtrip():
    from spark_rapids_tpu.columnar.device import batch_to_device
    from spark_rapids_tpu.memory import meta

    rb = pa.record_batch({
        "k": pa.array(np.arange(500, dtype=np.int64)),
        "s": pa.array([f"val_{i % 7}" for i in range(500)]),
    })
    batch = batch_to_device(rb, xp=np)
    for codec in (meta.CODEC_NONE, meta.CODEC_LZ4, meta.CODEC_ZSTD):
        data = meta.serialize_batch(batch, codec=codec)
        back = meta.deserialize_batch(data, xp=np)
        rb2 = pa.record_batch(
            {"k": pa.array(np.asarray(back.columns[0].data[:500])),
             "s": pa.array([s for s in _strings(back.columns[1], 500)])})
        assert rb2.column("k").to_pylist() == rb.column("k").to_pylist()
        assert rb2.column("s").to_pylist() == rb.column("s").to_pylist()


def _strings(col, n):
    from spark_rapids_tpu.columnar.device import column_to_arrow
    return column_to_arrow(col, n).to_pylist()


def test_spill_uses_default_codec():
    from spark_rapids_tpu.columnar.device import batch_to_device
    from spark_rapids_tpu.memory import meta
    from spark_rapids_tpu.memory.spill import SpillCatalog

    meta.set_default_codec("lz4")
    try:
        rb = pa.record_batch(
            {"v": pa.array(np.zeros(10_000, dtype=np.int64))})
        cat = SpillCatalog()
        sb = cat.register(batch_to_device(rb, xp=np))
        sb.spill_to_host()
        # highly repetitive data: compression must have shrunk it
        assert sb.host_size() < 10_000 * 8 // 10
        back = sb.get_batch(np)
        assert int(back.num_rows) == 10_000
        assert not np.asarray(back.columns[0].data[:10_000]).any()
    finally:
        meta.set_default_codec("none")


# -- the binary on disk is trusted by content, not by mtime -----------------

def _src_sha():
    from spark_rapids_tpu import native
    return native._sha256(native._SRC)


def test_stamp_ties_the_binary_to_its_source(tmp_path):
    import shutil
    from spark_rapids_tpu import native
    assert get_lib() is not None
    assert native._built_from(native._SO, _src_sha())
    so = str(tmp_path / "libtpu_native.so")
    shutil.copy(native._SO, so)
    # a copied binary with no stamp, a stamp for another source, and a
    # stamp whose binary was swapped: none is trusted, however new
    assert not native._built_from(so, _src_sha())
    shutil.copy(native._SO + ".sha256", so + ".sha256")
    assert native._built_from(so, _src_sha())
    assert not native._built_from(so, "0" * 64)
    with open(so, "ab") as f:
        f.write(b"\0")
    assert not native._built_from(so, _src_sha())


def test_no_compiler_is_the_zlib_mode_and_a_failed_compile_is_an_error(
        monkeypatch, tmp_path):
    import subprocess
    from spark_rapids_tpu import native
    monkeypatch.setattr(native, "_SO", str(tmp_path / "build" / "x.so"))
    monkeypatch.setattr(native, "_user_cache_so",
                        lambda: str(tmp_path / "cache" / "x.so"))
    monkeypatch.setattr(native.shutil, "which", lambda name: None)
    assert "no C++ compiler" in native._build()
    monkeypatch.setattr(native.shutil, "which", lambda name: "/usr/bin/g++")
    monkeypatch.setattr(
        native.subprocess, "run",
        lambda *a, **k: subprocess.CompletedProcess(a, 1, "", "boom"))
    with pytest.raises(RuntimeError, match="native build failed: boom"):
        native._build()
    assert not list(tmp_path.rglob("*.tmp"))
