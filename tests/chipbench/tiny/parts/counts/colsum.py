def work(config, chips):
    return {"bytes": config["rows"] * config["features"] * 4, "flops": config["rows"] * config["features"]}
