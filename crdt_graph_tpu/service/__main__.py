import sys

import jax

from ..utils import compcache
from .http import serve

compcache.enable()
jax.config.update("jax_enable_x64", True)
serve(int(sys.argv[1]) if len(sys.argv) > 1 else 8900)
