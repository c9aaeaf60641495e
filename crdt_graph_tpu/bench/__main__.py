import sys

import jax

from ..utils import compcache, hostenv

compcache.enable()
jax.config.update("jax_enable_x64", True)
hostenv.require_tpu("python -m crdt_graph_tpu.bench")

from .runner import main  # noqa: E402

main(sys.argv[1:])
