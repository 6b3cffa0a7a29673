"""The chip benchmark of EARL's RL training step (see BENCHMARK.json)."""
