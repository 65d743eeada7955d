"""Pallas TPU kernels for the four hot paths of the ParisKV pipeline
(paper §4.3's four CUDA kernels, re-targeted at TPU per DESIGN.md §2):

  collision/    Stage-I tier-weight accumulation over centroid ids
                (contiguous + block-table-indirect paged variants)
  bucket_topk/  histogram-based Top-β selection for small-range int scores
  rerank/       fused 4-bit unpack + RSQ-IP scoring of candidates
  gather_kv/    on-demand fetch of selected KV rows (UVA analogue)

Each subpackage ships the kernel (`pl.pallas_call` + BlockSpec), a jitted
wrapper (`ops.py`) and a pure-jnp oracle (`ref.py`).

Interpret-mode policy: Pallas kernels run *interpreted* (python emulation)
only where no TPU is attached. Every kernel entry point takes
``interpret=None`` and resolves it via :func:`resolve_interpret`:

  1. an explicit ``interpret=`` argument always wins;
  2. else the ``REPRO_PALLAS_INTERPRET`` env var (``0``/``1``) overrides —
     useful to force-interpret on TPU when debugging a kernel, or to
     assert-compile in CI images that advertise a TPU;
  3. else autodetect: compile on TPU, interpret everywhere else.

The backend is asked for on the first call, never at import: importing
this package must not initialise JAX's backends (that would take the
chip in every process that merely imports the kernels).
"""
import os


def resolve_interpret(interpret=None) -> bool:
    """Resolve an ``interpret=`` kernel argument (see module docstring)."""
    if interpret is not None:
        return bool(interpret)
    env = os.environ.get("REPRO_PALLAS_INTERPRET", "").strip()
    if env:                       # empty/unset → autodetect
        return env.lower() not in ("0", "false")
    import jax
    return jax.default_backend() != "tpu"
