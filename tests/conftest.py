import pathlib
import sys

# tests import the package from src/ (same as PYTHONPATH=src)
ROOT = pathlib.Path(__file__).resolve().parents[1]
SRC = ROOT / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

# NOTE: no XLA_FLAGS here on purpose — smoke tests and benches must see the
# real single CPU device.  Multi-device tests spawn subprocesses with
# --xla_force_host_platform_device_count set (tests/test_distributed.py).
