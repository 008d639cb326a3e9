"""The benchmark's own code: traffic, sender, sampler, reference, ledger,
trace reduction, peaks.  Nothing here imports the program except
`deploy.py`, which boots it."""
