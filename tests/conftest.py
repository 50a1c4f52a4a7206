import sys
from pathlib import Path

# src layout without install
sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

# NOTE: no XLA_FLAGS here on purpose — smoke tests and benches must see the
# real single CPU device; multi-device tests set it in a subprocess or a CI
# step of their own.
