"""The on-chip benchmark: ``python3 bench/run.py --workload <cell> ...``
(see ``bench/run.py``). Everything it needs to generate traffic, reduce
traces and decide ``correct`` lives under this directory."""
