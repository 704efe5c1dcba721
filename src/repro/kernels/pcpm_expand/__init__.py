from .kernel import arc_rows, fits, vmem_bytes, window_expand

__all__ = ["arc_rows", "fits", "vmem_bytes", "window_expand"]
