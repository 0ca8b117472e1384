"""``native.ensure_built`` judges a built library by the content of the
sources it was built from, not by file times: the chip tool copies the
tree as it stands on disk, and after a copy the times say nothing."""

import os

from horovod_tpu import native


def test_ensure_built_follows_source_content_not_file_times(
        tmp_path, monkeypatch):
    src = tmp_path / "coordinator.cc"
    build = tmp_path / "build"
    lib = build / "libhvdtpu_coord.so"
    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_SRC_COLL", str(tmp_path / "absent.cc"))
    monkeypatch.setattr(native, "_BUILD_DIR", str(build))
    monkeypatch.setattr(native, "_LIB", str(lib))
    monkeypatch.setattr(native, "_LIB_HASH", str(lib) + ".sha256")

    src.write_text('extern "C" int hvd_answer() { return 1; }\n')
    assert native.ensure_built()
    first = lib.read_bytes()
    built_at = os.stat(lib).st_mtime_ns

    # A newer file time with the same content is not stale.
    os.utime(src, ns=(built_at + 10**10, built_at + 10**10))
    assert native.ensure_built()
    assert os.stat(lib).st_mtime_ns == built_at

    # Other content under an OLDER file time is stale: rebuilt.
    src.write_text('extern "C" int hvd_answer() { return 20000; }\n')
    os.utime(src, ns=(built_at - 10**10, built_at - 10**10))
    assert native.ensure_built()
    assert lib.read_bytes() != first

    # A library with no hash beside it (an older build, a foreign
    # copy) is not trusted either.
    os.unlink(str(lib) + ".sha256")
    rebuilt_at = os.stat(lib).st_mtime_ns
    assert native.ensure_built()
    assert os.stat(lib).st_mtime_ns != rebuilt_at
