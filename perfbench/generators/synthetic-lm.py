"""Traffic generator `synthetic-lm`: writes nothing.  The job reads
`synthetic://lm?...`, token sequences that the program makes from the same
seed."""

#: No file, so no record codec to hold the job to.
CODEC = None


def training_data(cache_dir: str, data: dict, model: dict, seed: int):
    """-> the job's `--training_data` value."""
    return (
        f"synthetic://lm?n={int(data['sequences'])}&len={int(data['tokens'])}"
        f"&vocab={int(model['vocab_size'])}&seed={seed}"
    )
