"""Plain float32 references of the benchmark's models; they import nothing of the program."""
