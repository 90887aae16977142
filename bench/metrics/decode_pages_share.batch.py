"""How full the paged decode walk's budget is: the engine's
``decode_pages_live / decode_pages_budget`` over the window, in percent.
Set by the traffic's context lengths and the width buckets, on the host;
not a reading of the kernel."""

from lib.measure import decode_pages_share as read  # noqa: F401
