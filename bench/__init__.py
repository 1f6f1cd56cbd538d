"""The benchmark: one command, ``python3 bench/run.py``, and the data,
references and readers it finds by name (see ``bench/harness.py``)."""
