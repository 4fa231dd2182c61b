"""Share of the time inside the harness's step spans in which no op runs on the device, averaged over the four chips, in %."""
from chipbench.readers import idle_in_step_share as read  # noqa: F401
