"""Host milliseconds of exact ANI per directed pair in the sampled
(FracMinHash c=125) species job: the reading of
fragment_ani.host_ms_per_direction (the outermost `fragment-ani` stages
less their `fragment-ani.fetch` descendants, over the job's
`fragment-ani-directions`; the mean over the window's jobs). At c=125 a
query holds ~1/125 of a genome's k-mers, so this host work, not the
kernel, sets exact ANI's wall."""

from harness import load_reader

read = load_reader("fragment_ani.host_ms_per_direction")
