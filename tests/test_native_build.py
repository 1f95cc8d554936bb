"""The native core builds from any copy of the checkout (ISSUE 21).

A copied tree can carry a build directory configured for another path
(cmake then refuses at once), and the driver's xdist workers first-load
the core together.  The fast legs pin the cache check; the slow legs do
the real build in a copy (a full cmake+ninja build takes 10-60 s, which is
why they are marked slow).
"""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(REPO, "native")


def _foreign_cache(build_dir: str, home: str) -> None:
    os.makedirs(os.path.join(build_dir, "CMakeFiles"), exist_ok=True)
    with open(os.path.join(build_dir, "CMakeCache.txt"), "w") as f:
        f.write(f"CMAKE_HOME_DIRECTORY:INTERNAL={home}\n")
    for name in ("build.ninja", ".build.lock"):
        open(os.path.join(build_dir, name), "w").close()


@pytest.mark.parametrize("home", ["foreign", "own"])
def test_forget_foreign_cache(tmp_path, home):
    """A cache made for another source directory is dropped (cmake then
    configures afresh); one made for this directory is kept; the build
    directory and its lock file survive either way."""
    native = tmp_path / "native"
    native.mkdir()
    shutil.copy(os.path.join(NATIVE, "forget_foreign_cache.sh"), native)
    build = native / "build"
    _foreign_cache(str(build), "/elsewhere/repo/native" if home == "foreign"
                   else str(native))
    r = subprocess.run(["bash", str(native / "forget_foreign_cache.sh"),
                        "build"], capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    kept = home == "own"
    assert (build / "CMakeCache.txt").exists() == kept
    assert (build / "CMakeFiles").exists() == kept
    assert (build / "build.ninja").exists() == kept
    assert (build / ".build.lock").exists()


def _copy_tree(dst) -> str:
    """native/ sources (no build trees) + the loader, at another path."""
    shutil.copytree(NATIVE, dst / "native", ignore=lambda d, names: [
        n for n in names if n.startswith("build")
        and os.path.isdir(os.path.join(d, n))])
    os.makedirs(dst / "native" / "build")
    (dst / "brpc_tpu" / "_native").mkdir(parents=True)
    shutil.copy(os.path.join(REPO, "brpc_tpu", "_native", "__init__.py"),
                dst / "brpc_tpu" / "_native")
    return str(dst / "brpc_tpu" / "_native" / "libbrpc_tpu_core.so")


@pytest.mark.slow
def test_build_sh_in_a_copy_with_a_foreign_cache(tmp_path):
    so = _copy_tree(tmp_path)
    _foreign_cache(str(tmp_path / "native" / "build"), NATIVE)
    r = subprocess.run(["bash", str(tmp_path / "native" / "build.sh")],
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert os.path.exists(so)


LOAD = r"""
import importlib.util, sys
spec = importlib.util.spec_from_file_location("native_copy", sys.argv[1])
m = importlib.util.module_from_spec(spec)
spec.loader.exec_module(m)
L = m.lib()
assert L.trpc_workers is not None
print("LOADED", m._SO)
"""


@pytest.mark.slow
def test_concurrent_first_loads_build_once(tmp_path):
    """Two processes first-load the core from a copy with no library:
    both load the copy's own build, and the build ran once (the second
    waited on build.sh's lock and found nothing left to build)."""
    so = _copy_tree(tmp_path)
    loader = str(tmp_path / "brpc_tpu" / "_native" / "__init__.py")
    procs = [subprocess.Popen([sys.executable, "-c", LOAD, loader],
                              stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                              text=True) for _ in range(2)]
    for p in procs:
        out, err = p.communicate(timeout=600)
        assert p.returncode == 0, out + err[-2000:]
        assert f"LOADED {so}" in out
    with open(tmp_path / "native" / "build" / ".ninja_log") as f:
        # v5 log rows: start, end, mtime, output, command hash
        links = [ln for ln in f if not ln.startswith("#")
                 and ln.split("\t")[3] == "libbrpc_tpu_core.so"]
    assert len(links) == 1, links

