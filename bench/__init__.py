"""Cell benchmark of the fleet LoD service (see bench/harness.py)."""
