"""The comparisons that decide ``correct``, one a module, found by the
names a configuration's ``checks`` gives (``chipbench/check.py``)."""
