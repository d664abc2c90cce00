"""repro — locality-aware collectives, training and serving stack."""
