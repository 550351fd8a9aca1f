"""Plain references of the policies, independent of the program."""
