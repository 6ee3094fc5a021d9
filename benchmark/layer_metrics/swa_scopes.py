"""What the windowed-attention family's readers share: under which
scope of `harness/trace_reduce.py`'s tables each kind of layer's
operations stand.

`layers/transformer.GatedAttention` runs a layer with a window under
the named scope `window_attention` and one without under
`gated_attention`. `harness/trace_reduce.SCOPES` is the accepted
benchmark's constant and does not hold the first name (the PR that
brought these readers may add files only), so the reduction puts the
sliding layers' operations in its row `other`, beside what stands
under no scope of the list (the blocks' norms and residual adds, the
embedding's gather, the final norm). Only the `flash_attention`
kernels can be told apart there: no other layer's kernel stands under
`other`, so the banded kernel's roofline share looks in both rows. A
device time of the sliding layers waits for the scope (PERF.md
section 7 (0)): read from `other` it would hold the other layers'
work too."""

WINDOW_KERNELS = ("window_attention", "other")
FULL = ("gated_attention",)
