"""The package's Pallas kernels (TPU; off the chip they run in Pallas'
interpreter: ``blocked_attention.INTERPRET``).

Importing ``jax.experimental.pallas`` also imports Pallas' interpreter for
GPU kernels, for one ``isinstance`` check on ``pallas_call``'s ``interpret``
argument, and with it the LLVM and NVVM dialects: 0.5 s here, 1.0 s of the
1.45 s the import takes on the chip's host (launch log, PR 50), paid in the
set-up of every process that traces a kernel.  No kernel here is a GPU's, and
``pallas_call`` itself takes a stand-in where that interpreter cannot be
imported (``jax/_src/pallas/pallas_call.py``, the ``except ImportError`` at
its end): a ``None`` entry in ``sys.modules`` is Python's way of saying so.
Every kernel module of the package lies under this one, so the entry is made
before their first import of Pallas; a process that imported Pallas itself
first is left as it is.
"""

import sys

sys.modules.setdefault("jax._src.pallas.mosaic_gpu.interpret", None)
