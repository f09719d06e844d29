"""Share of the window in which no program ran on the chip
(1 minus the union of program intervals), averaged over the chips."""
from chipbench.readers import idle_pct as read  # noqa: F401
