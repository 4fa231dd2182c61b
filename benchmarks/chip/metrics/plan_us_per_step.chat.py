"""Host time of the plan layer (serve/plan.py) per scheduler step in the window, in microseconds: Scheduler.plan_time_s over steps."""
from chipbench.readers import plan_us_per_step as read  # noqa: F401
