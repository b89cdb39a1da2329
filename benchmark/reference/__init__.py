"""Plain references: one file for each configuration, found by its name.
``follow(config, traffic, seed, generations, precision)`` gives one snapshot for
each generation asked for; ``numbers(config, program, reference)`` the numbers
compared. A reference imports nothing of the program."""
