"""``ttft_p90_ms`` in a cell whose arrivals come in bursts, so that the
tail follows how each burst falls into the free slots and the prefill
queue: the scheduler's reaction to a burst, read beside the decode gaps
of the requests already running."""
from chipbench.metrics.ttft_p90_ms import read  # noqa: F401
