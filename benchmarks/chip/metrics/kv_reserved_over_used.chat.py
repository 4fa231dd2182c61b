"""Pages reserved over pages in use in the KV pool (serve/memory.py), the mean over the window's steps."""
from chipbench.readers import kv_reserved_over_used as read  # noqa: F401
