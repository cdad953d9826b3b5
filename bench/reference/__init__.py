"""Plain references that decide ``correct``. They import nothing of the
program under test and take nothing it made: weights come from the model
family's ``make`` (``bench/models/``) and the seed."""
