"""The serving child handed another weights' seed than the reference: the
checkpoint is drawn from ``weights_seed + 1``, the check still regenerates
``weights_seed``.  Started by the tests through ``run.py --sut``; everything
else is the real run."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks import sut

_write = sut.write_checkpoint
sut.write_checkpoint = lambda family, conf, seed, path: _write(family, conf, seed + 1, path)

if __name__ == "__main__":
    sys.exit(sut.main())
