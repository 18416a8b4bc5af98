"""One module a kernel: `work(ev, calls)` gives, for each of the kernel's
names in the trace, the operations and bytes the algorithm needs for the
calls the traced window holds (`calls(name)` counts them), computed from the
cell's shapes."""
