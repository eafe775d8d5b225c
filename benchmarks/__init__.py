"""The benchmark: everything `BENCHMARK.json` measures with lives here."""
