"""One module per *kind* of traffic, found by the ``generator`` name a
mix's file gives. A module has three functions, each taking the ``Run``
the harness built and the mix's ``params``:

- ``warmup(run, params)``: untimed traffic of the cell's own kind,
  deleted before it returns;
- ``prepare(run, params, seconds)``: whatever the window needs built
  beforehand (counted as set-up); its result is handed to ``window``;
- ``window(run, params, prepared, seconds)``: the timed window.
"""
