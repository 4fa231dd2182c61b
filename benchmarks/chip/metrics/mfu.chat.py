"""Model FLOPs of the real tokens of every step in the window over the steps' wall time times the chips' bf16 peak, in %."""
from chipbench.readers import mfu as read  # noqa: F401
