import sys
from pathlib import Path

# the benchmark's package (``bench``) lives at the repository's root
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
