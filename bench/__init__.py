"""On-chip benchmark of the SpTRSV solver: harness, traffic, oracle and trace
reduction.  Run ``python3 bench/run.py --help`` from the repository root."""
