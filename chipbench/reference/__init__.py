"""Plain references the benchmark compares the program's answers with.
They import nothing of the program."""
