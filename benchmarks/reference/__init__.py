"""Plain references. Nothing here imports the program."""
