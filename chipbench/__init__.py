"""The chip benchmark of the ADEL-FL round runtime (see ``run.py``)."""
