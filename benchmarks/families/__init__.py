"""The program's side of each model family: how the system under test is
built and fed. One module a family, named in the configuration's file."""
