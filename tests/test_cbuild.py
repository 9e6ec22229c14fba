"""Native libraries are named by a hash of what built them.

A library copied in with a checkout, or left behind by an older source,
carries another name than the one the current source builds, so it is
never loaded against that source.
"""

import ctypes
import shutil

import pytest

from galah_tpu.utils import cbuild

pytestmark = pytest.mark.skipif(shutil.which("cc") is None,
                                reason="no C compiler")

_SRC = "int galah_test_value(void) { return %d; }\n"


def _build(tmp_path, monkeypatch, value):
    csrc = tmp_path / "csrc"
    csrc.mkdir(exist_ok=True)
    (csrc / "probe.c").write_text(_SRC % value)
    monkeypatch.setattr(cbuild, "_CSRC", csrc)
    monkeypatch.setattr(cbuild, "_FAILED", {})
    (tmp_path / "out").mkdir(exist_ok=True)
    lib = cbuild.build_and_load("probe.c", "_libprobe", tmp_path / "out")
    fn = lib.galah_test_value
    fn.restype = ctypes.c_int
    return fn(), sorted(p.name for p in (tmp_path / "out").iterdir())


def test_edited_source_builds_a_new_library(tmp_path, monkeypatch):
    first, libs1 = _build(tmp_path, monkeypatch, 1)
    second, libs2 = _build(tmp_path, monkeypatch, 2)
    assert (first, second) == (1, 2)
    assert len(libs1) == 1 and len(libs2) == 2
    assert libs1[0] in libs2


def test_unchanged_source_reuses_its_library(tmp_path, monkeypatch):
    _build(tmp_path, monkeypatch, 7)
    (lib,) = (tmp_path / "out").iterdir()
    mtime = lib.stat().st_mtime_ns
    value, libs = _build(tmp_path, monkeypatch, 7)
    assert value == 7 and libs == [lib.name]
    assert lib.stat().st_mtime_ns == mtime


def test_build_key_covers_source_compiler_and_flags():
    base = cbuild.build_key(b"src", "cc", ())
    assert base == cbuild.build_key(b"src", "cc", ())
    assert len({base, cbuild.build_key(b"src2", "cc", ()),
                cbuild.build_key(b"src", "clang", ()),
                cbuild.build_key(b"src", "cc", ("-lz",))}) == 4
