"""On-chip benchmark of the federated round engine (see ``bench/run.py``)."""
