"""As mfu, over the steps that carry a prefill chunk only, in %."""
from chipbench.readers import mfu


def read(run):
    return mfu(run, chunk_only=True)
