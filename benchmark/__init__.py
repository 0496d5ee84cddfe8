"""The benchmark: see README.md beside this file. Nothing here is imported by the program."""
