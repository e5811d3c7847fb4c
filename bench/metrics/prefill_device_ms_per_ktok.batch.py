"""Model step, in cells that report ``itl_p95_ms`` and not
``ttft_p50_ms``: the reading of ``prefill_device_ms_per_ktok``.  There
whole prompts are prefilled inside the engine step, so their time sets
the slow gaps between tokens."""

from bench import harness

read = harness.load_reader("prefill_device_ms_per_ktok")
