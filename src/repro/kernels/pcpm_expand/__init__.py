from .kernel import fits, vmem_bytes, window_expand

__all__ = ["fits", "vmem_bytes", "window_expand"]
