"""Share of its memory roofline that the exact-ANI membership kernel
reached in the checked job of the sampled (FracMinHash c=125) species
job: the reading of fragment_kernel_roofline, whose bytes
(kernel_bytes.fragment_membership_bytes) come from the reference's
sampled sizes (`JobResult.sizes`: query positions and distinct k-mers
as sampled), at the chip's HBM bandwidth, over the
`_window_hits_jit` device time. With ~24k-hash queries one launch per
direction sets the pace, so this reads the per-launch floor."""

from harness import load_reader

read = load_reader("fragment_kernel_roofline")
