"""The harness: loader, traffic generator, window arithmetic, trace
reduction, peaks and the output line.  It knows no cell, configuration or
metric by name; those are files that ``BENCHMARK.json`` names."""
