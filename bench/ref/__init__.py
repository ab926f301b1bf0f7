"""Plain NumPy references and data generators: the yardstick that
decides ``correct``.  Nothing here imports the program."""
