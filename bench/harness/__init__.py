"""General benchmark machinery: start-up, generators, cell runners, trace
reduction.  Nothing here names a cell, a configuration or a metric; those
are found by name under ``bench/configs``, ``bench/traffic``,
``bench/metrics``, ``bench/checks`` and ``bench/roofline``."""
