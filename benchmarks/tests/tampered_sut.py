"""The serving child with the timed path broken underneath: every served token
whose id is a multiple of 5 is altered where the engine hands it to the stream.
Started by the tests through ``run.py --sut``; everything else is the real run."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks import sut
from django_assistant_bot_tpu.serving.streaming import TokenStream

_push = TokenStream.push_token
TokenStream.push_token = lambda self, tok, **kw: _push(self, 33 + (tok - 32) % 90 if tok % 5 == 0 else tok, **kw)

if __name__ == "__main__":
    sys.exit(sut.main())
