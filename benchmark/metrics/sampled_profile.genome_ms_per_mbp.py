"""Milliseconds of profile work per Mbp profiled in the sampled
(FracMinHash c=125) species job: the reading of
profile_genomes.genome_ms_per_mbp (`profile.read` + `profile.build`
thread-seconds over `profile-bp`; the mean over the window's jobs). The
masked C walk keeps ~1/125 of the k-mers, so the FASTA read is a larger
share here than in the dense job."""

from harness import load_reader

read = load_reader("profile_genomes.genome_ms_per_mbp")
