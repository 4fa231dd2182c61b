"""Model FLOPs of the real tokens of every step in the window over the steps' wall time times the four chips' bf16 peak, in %. In a closed loop it tracks the sweep's throughput."""
from chipbench.readers import mfu as read  # noqa: F401
