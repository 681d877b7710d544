"""The native loader (kubernetes_tpu/native/__init__.py) keys the built
extension on the CONTENT of _hotpath.c, never on mtimes."""

import os
import shutil

from kubernetes_tpu import native


def test_rebuilds_when_content_changes_under_an_unchanged_mtime(tmp_path):
    src = tmp_path / "_hotpath.c"
    shutil.copyfile(native._SRC, src)
    first = native.ensure_built(str(src), str(tmp_path))
    assert os.path.exists(first)
    assert native.ensure_built(str(src), str(tmp_path)) == first

    stat = os.stat(src)
    with open(src, "a") as f:
        f.write("\n/* edited */\n")
    os.utime(src, ns=(stat.st_atime_ns, stat.st_mtime_ns))
    assert os.stat(src).st_mtime_ns == stat.st_mtime_ns

    second = native.ensure_built(str(src), str(tmp_path))
    assert second != first
    assert os.path.exists(second)
    # the artefact of the old content is not left behind to be loaded
    assert not os.path.exists(first)
    module = native._load(second)
    assert callable(module.cow_clone)


def test_foreign_binary_under_the_old_name_is_not_loaded(tmp_path):
    """A tree copied with someone else's un-keyed .so: the loader builds
    from the source it holds and removes the stray binary."""
    src = tmp_path / "_hotpath.c"
    shutil.copyfile(native._SRC, src)
    stray = tmp_path / ("_hotpath" + native._EXT)
    stray.write_bytes(b"not an extension")
    so = native.ensure_built(str(src), str(tmp_path))
    assert so != str(stray)
    assert not stray.exists()
    assert callable(native._load(so).cow_clone)


def test_build_failure_is_reported(tmp_path):
    src = tmp_path / "_hotpath.c"
    src.write_text("this is not C\n")
    try:
        native.ensure_built(str(src), str(tmp_path))
    except RuntimeError as e:
        assert "native build" in str(e)
    else:
        raise AssertionError("a broken source built")


def test_the_loaded_extension_is_the_keyed_artefact():
    assert native.hotpath is not None, native.build_error
    assert native.hotpath.__file__ == native.artefact_path()
    assert native.build_error == ""
