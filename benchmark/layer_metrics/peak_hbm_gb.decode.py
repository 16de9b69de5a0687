"""Peak bytes on the fullest chip when the window closed: live buffers plus
what the allocator reserved for compiled programs' temporaries."""
from benchmark.lib.layer_common import peak_hbm_gb as read  # noqa: F401
