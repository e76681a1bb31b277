"""The benchmark's harness: everything the yardstick is made of lives here
(see benchmarks/README.md). Found by name from BENCHMARK.json; holds no
cell's, configuration's or metric's name in its code."""
