import sys
from pathlib import Path

# the benchmark package lives at the repository root, beside src/
ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
