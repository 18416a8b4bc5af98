"""One module a traffic mode: `train` and `serve` (open and closed loop).
`run(cell, env)` measures one window and returns the evidence the
arithmetic and the per-layer readers work from."""
