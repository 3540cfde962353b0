import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# runs in the tests compile for the CPU: keep their persistent cache
# apart from the chip's, in a directory of this session
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    import tempfile
    os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
        prefix="bench-tests-jax-cache-")
