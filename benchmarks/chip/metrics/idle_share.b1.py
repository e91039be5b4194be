"""Share of the traced window in which no operation ran on the device,
in % (``harness.idle_share``)."""
from benchmarks.chip.harness import idle_share as read  # noqa: F401
