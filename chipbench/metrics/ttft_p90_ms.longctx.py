"""``ttft_p90_ms`` in a cell whose first tokens wait in the queue of
prefill chunks (one chunk an iteration), so that the tail follows the
order of the bursts: the scheduler's prefill queue, read beside the
iterations it shares with decode."""
from chipbench.metrics.ttft_p90_ms import read  # noqa: F401
