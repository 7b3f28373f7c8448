"""One module per kernel: ``work(**shape) -> (flops, bytes)``, the
operations and HBM bytes the kernel's job needs, computed from shapes.
A roofline share divides the least time they allow by the kernel's time."""
